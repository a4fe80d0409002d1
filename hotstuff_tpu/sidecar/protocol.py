"""Wire protocol between consensus nodes (C++) and the TPU verify sidecar.

The sidecar plays the role the reference gives its in-process
``SignatureService`` + ``Signature::verify_batch`` (crypto/src/lib.rs:210-254):
a node ships the votes of a quorum certificate to a long-lived process that
owns the accelerator, and gets back a per-signature validity mask.  Because
the node data plane is C++ and the device engine is JAX, the boundary is a
localhost TCP socket with length-delimited frames — the same framing idiom
the reference uses between replicas (4-byte length prefix,
network/src/receiver.rs:70).

Frame layout (all integers little-endian unless noted):

    [u32 BIG-endian frame length][payload]

Request payload:
    u8  opcode      1 = VERIFY_BATCH, 2 = PING
    u32 request id  echoed in the reply (lets a client pipeline requests)
    u32 count N     number of signature records (0 for PING)
    u16 msg_len M   byte length of each message (digests: 32)
    [32 bytes context tag — protocol v5, OPTIONAL: the block digest this
     verify serves; all-zero = none; discriminated by frame length]
    N * (M bytes msg | 32 bytes pubkey | 64 bytes signature)

Reply payload:
    u8  opcode echo
    u32 request id echo
    u32 count N
    N bytes of 0/1 validity
"""

from __future__ import annotations

import struct
from dataclasses import dataclass

OP_VERIFY_BATCH = 1
OP_PING = 2
# BLS extension (the reference's bls branch capability): aggregate verify
# over one common message (the QC shape), G1 pks (96 B uncompressed) and
# G2 signatures (192 B uncompressed), plus signing for the node's
# SignatureService when the committee runs scheme=bls.
OP_BLS_VERIFY_AGG = 3
OP_BLS_SIGN = 4
# Per-vote variant used by the C++ node (it cannot aggregate G2 points):
# the sidecar aggregates the signatures itself, then runs the same
# common-message 2-pairing check. Reply: one 0/1 byte.
OP_BLS_VERIFY_VOTES = 5
# Multi-digest variant (the TC shape: per-vote signatures over DISTINCT
# digests, consensus/src/messages.rs:307-313): one RPC, verified as
# prod e(pk_i, H(m_i)) == e(g1, sum sig_i) under a single final
# exponentiation. Reply: one 0/1 byte.
OP_BLS_VERIFY_MULTI = 6
# Protocol v2 (verifysched): request CLASS rides in the opcode, so v1
# clients keep their correct latency-class behavior without a flag day.
# OP_VERIFY_BATCH is the latency class (consensus QC/TC verifies, bounds
# commit latency); OP_VERIFY_BULK is the bulk class (mempool / offchain
# batch verifies — throughput-bound, yields to latency work).  Same
# frame layout as OP_VERIFY_BATCH in both directions.
OP_VERIFY_BULK = 7
# Scheduler-telemetry snapshot: header-only request (count 0, like
# PING); the reply body is one UTF-8 JSON object (the engine's
# stats_snapshot() dict — schema in sidecar/sched/stats.py), framed by
# encode_reply_raw with count = body length.
OP_STATS = 8
# Protocol v3 (graftchaos): configure the sidecar's fault-injection hook.
# The request body is one UTF-8 JSON object (count = body length, msg_len
# 0; spec schema in sidecar/service.ChaosState: bounded reply delay,
# forced connection drops, forced queue-full sheds, clear).  Reply is a
# one-byte mask: [1] applied, [0] refused (server runs without --chaos).
# Only honored behind the explicit --chaos flag — a production sidecar
# cannot be degraded over the wire.
OP_CHAOS = 9
# Protocol v4 (graftsurge): explicit BUSY reply.  When a class queue is
# full (or the surge admission controller sheds), the sidecar answers
# with OP_BUSY — request id echoed, count = 2, body one u16 LE
# retry-after hint in milliseconds — instead of the v2/v3 empty-count
# echo of the request opcode.  Reply-only: a request frame carrying
# OP_BUSY is malformed.  Clients back off for ~the hint (python raises
# SidecarOverloaded with retry_after_ms; the C++ node falls back to host
# verify, its in-flight AIMD already pacing resubmission).
OP_BUSY = 10
# Protocol v6 (graftfleet): optional session HELLO.  A client that wants
# a tenant identity (a node in a shared sidecar fleet) sends OP_HELLO
# once after connecting: count carries the CLIENT's protocol version,
# msg_len the tenant-id byte length, body the tenant id (UTF-8,
# [A-Za-z0-9._-], 1..TENANT_MAX_LEN bytes).  The reply echoes the
# SERVER's protocol version (one byte) followed by the accepted tenant
# id, so a version-skewed pair is visible at session start instead of
# mid-verify.  HELLO is OPTIONAL: a connection that never sends one is
# mapped to DEFAULT_TENANT and behaves exactly like a v5 client — every
# pre-fleet client and test stays valid without a flag day.
OP_HELLO = 11

# Version of this wire protocol, bumped when the opcode set or any frame
# layout changes (v2: OP_VERIFY_BULK + OP_STATS; v3: OP_CHAOS; v4:
# OP_BUSY retry-after replies; v5: the graftscope context tag below; v6:
# the graftfleet OP_HELLO tenant handshake).
# Mirrored by the C++ client's kProtocolVersion; graftlint's wire
# cross-checker pins the pair.  Replies an unknown-opcode ValueError on
# older peers rather than desyncing, so the constant is documentation +
# lint anchor, not a handshake — OP_HELLO echoes it for visibility but
# no version is rejected.
PROTOCOL_VERSION = 6

# graftfleet tenant identity: connections that never send OP_HELLO — the
# unix-era single-node clients — act under this tenant, so the fairness
# layer sees exactly one tenant and scheduling is unchanged.
DEFAULT_TENANT = "default"
TENANT_MAX_LEN = 64
_TENANT_OK = frozenset(
    "abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789._-")

# Protocol v5 (graftscope): OP_VERIFY_BATCH / OP_VERIFY_BULK — and, since
# the BLS trace-parity work, OP_BLS_VERIFY_VOTES / OP_BLS_VERIFY_MULTI —
# requests may carry a 32-byte CONTEXT TAG between the fixed header and
# the records: the block digest whose certificate this verify serves.
# The sidecar tags its request/queue/pack/dispatch/device/reply spans with
# it, which is what lets obs/trace.py nest the sidecar stage chain
# (device time included) inside that block's verify segment in
# logs/trace.json — for scheme=bls runs exactly like EdDSA ones.
#
# The tag is OPTIONAL and self-describing by frame length: a verify
# payload is either header + N records (legacy, ctx None) or header +
# 32 tag bytes + N records — unambiguous because an Ed25519 record is
# msg_len + 96 >= 96 bytes and a BLS record is >= 288 bytes, so 32
# extra bytes can never alias a record count.
# Writers emit the tag only when they HAVE a block context (the C++
# client's no-context frames stay byte-identical to v4, so a node
# upgraded before its sidecar keeps verifying), an ALL-ZERO tag is
# tolerated and decodes as ctx None, and legacy tag-less frames stay
# valid forever.
CTX_LEN = 32
ZERO_CTX = b"\x00" * CTX_LEN

# Backpressure contract: v2/v3 shed replies were an EMPTY body (count 0)
# for a request that carried records — unambiguous, because a real
# verdict mask always has exactly the request's record count.  v4 sheds
# reply OP_BUSY with a retry-after hint instead; clients keep accepting
# the empty-body form so a version-skewed sidecar still reads as
# overload, never as a verdict.

_HDR = struct.Struct("<BIIH")  # opcode, request id, count, msg_len
_REPLY_HDR = struct.Struct("<BII")
_BUSY_BODY = struct.Struct("<H")  # retry-after hint, ms

MAX_FRAME = 64 * 1024 * 1024

# Fixed record sizes shared with the C++ node (crypto/crypto.hpp,
# crypto/sidecar_client.cpp).  graftlint's wire cross-checker asserts the
# two sides agree — edit BOTH or the gate fails.
DIGEST_LEN = 32       # SHA-512/32 digests: the only msg the node sends
ED_PK_LEN = 32
ED_SIG_LEN = 64
BLS_PK_LEN = 96
BLS_SIG_LEN = 192
BLS_SK_LEN = 48


@dataclass
class VerifyRequest:
    request_id: int
    msgs: list
    pks: list
    sigs: list
    # graftscope (protocol v5): the 32-byte block-digest context tag, or
    # None when the frame carried none (legacy frame or all-zero tag).
    ctx: bytes | None = None


@dataclass
class BlsAggRequest:
    request_id: int
    msg: bytes
    agg_sig: bytes        # 192 B uncompressed G2
    pks: list             # n x 96 B uncompressed G1


@dataclass
class BlsSignRequest:
    request_id: int
    msg: bytes
    sk: bytes             # 48 B big-endian scalar


@dataclass
class BlsVotesRequest:
    request_id: int
    msg: bytes
    pks: list             # n x 96 B uncompressed G1
    sigs: list            # n x 192 B uncompressed G2
    # graftscope (protocol v5): block-digest context tag, as on
    # VerifyRequest — BLS spans join block traces like EdDSA ones.
    ctx: bytes | None = None


@dataclass
class BlsMultiRequest:
    request_id: int
    msgs: list            # n x msg_len digests (distinct per vote)
    pks: list             # n x 96 B uncompressed G1
    sigs: list            # n x 192 B uncompressed G2
    ctx: bytes | None = None


@dataclass
class ChaosRequest:
    request_id: int
    spec: dict            # fault knobs (service.ChaosState.configure)


@dataclass
class HelloRequest:
    request_id: int
    version: int          # the CLIENT's protocol version (informational)
    tenant: str           # validated tenant id ([A-Za-z0-9._-]{1,64})


def validate_tenant(raw) -> str:
    """Tenant-id validation shared by the codec and the server: UTF-8
    (or str), 1..TENANT_MAX_LEN bytes, charset [A-Za-z0-9._-].  Raises
    ValueError on anything else — a tenant id keys scheduler lanes and
    telemetry dicts, so garbage must die at the frame boundary."""
    if isinstance(raw, bytes):
        try:
            tenant = raw.decode("utf-8")
        except UnicodeDecodeError as e:
            raise ValueError(f"bad tenant id: {e}")
    else:
        tenant = raw
    if not tenant or len(tenant.encode("utf-8")) > TENANT_MAX_LEN:
        raise ValueError(
            f"bad tenant id length: 1..{TENANT_MAX_LEN} bytes required")
    if not set(tenant) <= _TENANT_OK:
        raise ValueError("bad tenant id: charset is [A-Za-z0-9._-]")
    return tenant


def encode_request(request_id: int, msgs, pks, sigs,
                   opcode: int = OP_VERIFY_BATCH,
                   ctx: bytes | None = None) -> bytes:
    """``ctx`` (protocol v5) attaches the 32-byte block-digest context
    tag after the header; None emits the legacy tag-less frame (an
    all-zero ctx is legal and decodes back as None)."""
    n = len(msgs)
    assert len(pks) == n and len(sigs) == n
    assert opcode in (OP_VERIFY_BATCH, OP_VERIFY_BULK)
    msg_len = len(msgs[0]) if n else 0
    parts = [_HDR.pack(opcode, request_id, n, msg_len)]
    if ctx is not None:
        assert len(ctx) == CTX_LEN
        parts.append(ctx)
    for m, p, s in zip(msgs, pks, sigs):
        assert len(m) == msg_len and len(p) == ED_PK_LEN \
            and len(s) == ED_SIG_LEN
        parts.append(m)
        parts.append(p)
        parts.append(s)
    payload = b"".join(parts)
    return struct.pack(">I", len(payload)) + payload


def encode_ping(request_id: int = 0) -> bytes:
    payload = _HDR.pack(OP_PING, request_id, 0, 0)
    return struct.pack(">I", len(payload)) + payload


def encode_stats_request(request_id: int = 0) -> bytes:
    """Header-only telemetry request (count 0, like PING)."""
    payload = _HDR.pack(OP_STATS, request_id, 0, 0)
    return struct.pack(">I", len(payload)) + payload


def encode_stats_reply(request_id: int, snapshot: dict) -> bytes:
    """Stats snapshot dict -> raw-reply frame (UTF-8 JSON body)."""
    import json

    body = json.dumps(snapshot, sort_keys=True).encode("utf-8")
    # graftlint: disable=unverified-flow-to-sink (locally-built telemetry snapshot, carries no verdict bits)
    return encode_reply_raw(OP_STATS, request_id, body)


def decode_stats_body(body: bytes) -> dict:
    """Raw OP_STATS reply body -> snapshot dict (ValueError on garbage,
    same contract as decode_request)."""
    import json

    try:
        out = json.loads(body.decode("utf-8"))
    except (UnicodeDecodeError, ValueError) as e:
        raise ValueError(f"bad stats body: {e}")
    if not isinstance(out, dict):
        raise ValueError("stats body is not a JSON object")
    return out


def encode_busy_reply(request_id: int, retry_after_ms: int) -> bytes:
    """Queue-full shed -> OP_BUSY reply carrying the retry-after hint
    (clamped to the u16 range; 0 means 'immediately' and is legal)."""
    ms = max(0, min(0xFFFF, int(retry_after_ms)))
    return encode_reply_raw(OP_BUSY, request_id, _BUSY_BODY.pack(ms))


def decode_busy_body(body: bytes) -> int:
    """OP_BUSY reply body -> retry-after ms (ValueError on garbage)."""
    if len(body) != _BUSY_BODY.size:
        raise ValueError(f"bad busy body: {len(body)} byte(s)")
    return _BUSY_BODY.unpack(body)[0]


def encode_hello_request(request_id: int, tenant: str,
                         version: int = PROTOCOL_VERSION) -> bytes:
    """Session HELLO (protocol v6): tenant id in the body, the client's
    protocol version riding the count field (header-only otherwise)."""
    body = validate_tenant(tenant).encode("utf-8")
    payload = _HDR.pack(OP_HELLO, request_id, version, len(body)) + body
    return struct.pack(">I", len(payload)) + payload


def encode_hello_reply(request_id: int, tenant: str) -> bytes:
    """HELLO ack: one byte of SERVER protocol version, then the accepted
    tenant id — the version echo that makes wire skew visible at session
    start."""
    body = bytes([PROTOCOL_VERSION]) + tenant.encode("utf-8")
    return encode_reply_raw(OP_HELLO, request_id, body)


def decode_hello_body(body: bytes):
    """HELLO reply body -> (server protocol version, tenant id);
    ValueError on garbage."""
    if not body:
        raise ValueError("empty hello reply body")
    return body[0], validate_tenant(body[1:])


def encode_chaos_request(request_id: int, spec: dict) -> bytes:
    """Chaos-hook configuration -> request frame (UTF-8 JSON body riding
    the count field as its byte length, like the OP_STATS reply)."""
    import json

    body = json.dumps(spec, sort_keys=True).encode("utf-8")
    payload = _HDR.pack(OP_CHAOS, request_id, len(body), 0) + body
    return struct.pack(">I", len(payload)) + payload


def encode_bls_agg_request(request_id: int, msg: bytes, agg_sig: bytes,
                           pks) -> bytes:
    assert len(agg_sig) == BLS_SIG_LEN
    assert all(len(p) == BLS_PK_LEN for p in pks)
    payload = (_HDR.pack(OP_BLS_VERIFY_AGG, request_id, len(pks), len(msg))
               + msg + agg_sig + b"".join(pks))
    return struct.pack(">I", len(payload)) + payload


def encode_bls_sign_request(request_id: int, msg: bytes, sk: bytes) -> bytes:
    assert len(sk) == BLS_SK_LEN
    payload = (_HDR.pack(OP_BLS_SIGN, request_id, 1, len(msg)) + msg + sk)
    return struct.pack(">I", len(payload)) + payload


def encode_bls_votes_request(request_id: int, msg: bytes, pks, sigs,
                             ctx: bytes | None = None) -> bytes:
    """``ctx`` (protocol v5) rides between header and the shared message,
    the same slot as OP_VERIFY_BATCH; None emits the legacy frame."""
    assert len(pks) == len(sigs)
    recs = b"".join(p + s for p, s in zip(pks, sigs))
    parts = [_HDR.pack(OP_BLS_VERIFY_VOTES, request_id, len(pks), len(msg))]
    if ctx is not None:
        assert len(ctx) == CTX_LEN
        parts.append(ctx)
    parts.append(msg)
    parts.append(recs)
    payload = b"".join(parts)
    return struct.pack(">I", len(payload)) + payload


def encode_bls_multi_request(request_id: int, msgs, pks, sigs,
                             ctx: bytes | None = None) -> bytes:
    n = len(msgs)
    assert len(pks) == n and len(sigs) == n
    msg_len = len(msgs[0]) if n else 0
    assert all(len(m) == msg_len for m in msgs)
    recs = b"".join(m + p + s for m, p, s in zip(msgs, pks, sigs))
    parts = [_HDR.pack(OP_BLS_VERIFY_MULTI, request_id, n, msg_len)]
    if ctx is not None:
        assert len(ctx) == CTX_LEN
        parts.append(ctx)
    parts.append(recs)
    payload = b"".join(parts)
    return struct.pack(">I", len(payload)) + payload


# graftlint: sanitizes=frame-structure
def decode_request(payload: bytes):
    """payload (no length prefix) -> (opcode, request dataclass).

    Contract: any malformed frame raises ValueError (callers close the
    connection on it); nothing else escapes."""
    try:
        opcode, request_id, n, msg_len = _HDR.unpack_from(payload, 0)
    except struct.error as e:
        raise ValueError(f"short frame: {e}")
    if opcode not in (OP_VERIFY_BATCH, OP_VERIFY_BULK, OP_PING, OP_STATS,
                      OP_BLS_VERIFY_AGG, OP_BLS_SIGN, OP_BLS_VERIFY_VOTES,
                      OP_BLS_VERIFY_MULTI, OP_CHAOS, OP_HELLO):
        raise ValueError(f"unknown opcode {opcode}")
    if opcode in (OP_PING, OP_STATS):
        return opcode, VerifyRequest(request_id, [], [], [])
    if opcode == OP_HELLO:
        # count = client protocol version, msg_len = tenant byte length;
        # a trailing-garbage or truncated body is malformed like any
        # other frame (never a silent partial tenant id).
        body = payload[_HDR.size:]
        if len(body) != msg_len:
            raise ValueError(
                f"bad hello frame: {len(body)} body byte(s), "
                f"msg_len {msg_len}")
        return opcode, HelloRequest(request_id, n, validate_tenant(body))
    if opcode == OP_CHAOS:
        import json

        body = payload[_HDR.size:]
        if len(body) != n:
            raise ValueError("bad chaos frame")
        try:
            spec = json.loads(body.decode("utf-8"))
        except (UnicodeDecodeError, ValueError) as e:
            raise ValueError(f"bad chaos body: {e}")
        if not isinstance(spec, dict):
            raise ValueError("chaos body is not a JSON object")
        return opcode, ChaosRequest(request_id, spec)
    if opcode == OP_BLS_VERIFY_AGG:
        off = _HDR.size
        msg = payload[off:off + msg_len]
        off += msg_len
        agg = payload[off:off + BLS_SIG_LEN]
        off += BLS_SIG_LEN
        if len(payload) != off + n * BLS_PK_LEN:
            raise ValueError("bad BLS aggregate frame")
        pks = [payload[off + i * BLS_PK_LEN:off + (i + 1) * BLS_PK_LEN]
               for i in range(n)]
        return opcode, BlsAggRequest(request_id, msg, agg, pks)
    if opcode == OP_BLS_SIGN:
        off = _HDR.size
        msg = payload[off:off + msg_len]
        sk = payload[off + msg_len:off + msg_len + BLS_SK_LEN]
        if len(payload) != off + msg_len + BLS_SK_LEN:
            raise ValueError("bad BLS sign frame")
        return opcode, BlsSignRequest(request_id, msg, sk)
    if opcode == OP_BLS_VERIFY_VOTES:
        off = _HDR.size
        rec = BLS_PK_LEN + BLS_SIG_LEN
        # v5 context tag: frame length discriminates (a BLS record is
        # 288 bytes, so 32 tag bytes can never alias a record count).
        ctx = None
        if len(payload) == off + CTX_LEN + msg_len + n * rec:
            tag = payload[off:off + CTX_LEN]
            ctx = None if tag == ZERO_CTX else tag
            off += CTX_LEN
        msg = payload[off:off + msg_len]
        off += msg_len
        if len(payload) != off + n * rec:
            raise ValueError("bad BLS votes frame")
        pks, sigs = [], []
        for i in range(n):
            base = off + i * rec
            pks.append(payload[base:base + BLS_PK_LEN])
            sigs.append(payload[base + BLS_PK_LEN:base + rec])
        return opcode, BlsVotesRequest(request_id, msg, pks, sigs, ctx=ctx)
    if opcode == OP_BLS_VERIFY_MULTI:
        off = _HDR.size
        rec = msg_len + BLS_PK_LEN + BLS_SIG_LEN
        ctx = None
        if len(payload) == off + CTX_LEN + n * rec:
            tag = payload[off:off + CTX_LEN]
            ctx = None if tag == ZERO_CTX else tag
            off += CTX_LEN
        if len(payload) != off + n * rec:
            raise ValueError("bad BLS multi frame")
        msgs, pks, sigs = [], [], []
        for i in range(n):
            base = off + i * rec
            msgs.append(payload[base:base + msg_len])
            pks.append(payload[base + msg_len:base + msg_len + BLS_PK_LEN])
            sigs.append(payload[base + msg_len + BLS_PK_LEN:base + rec])
        return opcode, BlsMultiRequest(request_id, msgs, pks, sigs, ctx=ctx)
    rec = msg_len + ED_PK_LEN + ED_SIG_LEN
    off = _HDR.size
    # Protocol v5 context tag: frame length discriminates (a record is
    # msg_len + 96 >= 96 bytes, so the 32 tag bytes never alias one).
    ctx = None
    if len(payload) == off + CTX_LEN + n * rec:
        tag = payload[off:off + CTX_LEN]
        ctx = None if tag == ZERO_CTX else tag
        off += CTX_LEN
    elif len(payload) != off + n * rec:
        raise ValueError(
            f"bad frame: expected {off + n * rec} "
            f"(or +{CTX_LEN} tagged) bytes, got {len(payload)}")
    msgs, pks, sigs = [], [], []
    for _ in range(n):
        msgs.append(payload[off:off + msg_len])
        off += msg_len
        pks.append(payload[off:off + ED_PK_LEN])
        off += ED_PK_LEN
        sigs.append(payload[off:off + ED_SIG_LEN])
        off += ED_SIG_LEN
    return opcode, VerifyRequest(request_id, msgs, pks, sigs, ctx=ctx)


def encode_reply(opcode: int, request_id: int, mask) -> bytes:
    body = bytes(bytearray(int(bool(b)) for b in mask))
    payload = _REPLY_HDR.pack(opcode, request_id, len(body)) + body
    return struct.pack(">I", len(payload)) + payload


def encode_reply_raw(opcode: int, request_id: int, body: bytes) -> bytes:
    """Reply whose body is raw bytes (BLS signatures) rather than a 0/1
    mask; same framing, count = body length."""
    payload = _REPLY_HDR.pack(opcode, request_id, len(body)) + body
    return struct.pack(">I", len(payload)) + payload


def decode_reply(payload: bytes):
    opcode, request_id, n = _REPLY_HDR.unpack_from(payload, 0)
    mask = [bool(b) for b in payload[_REPLY_HDR.size:_REPLY_HDR.size + n]]
    return opcode, request_id, mask


def decode_reply_raw(payload: bytes):
    opcode, request_id, n = _REPLY_HDR.unpack_from(payload, 0)
    return opcode, request_id, payload[_REPLY_HDR.size:_REPLY_HDR.size + n]


def read_frame(sock) -> bytes:
    """Blocking read of one length-delimited frame from a socket."""
    hdr = _read_exact(sock, 4)
    (length,) = struct.unpack(">I", hdr)
    if length > MAX_FRAME:
        raise ValueError(f"frame too large: {length}")
    return _read_exact(sock, length)


def _read_exact(sock, n: int) -> bytes:
    buf = bytearray()
    while len(buf) < n:
        # The bound lives on the socket, not here: every CLIENT sets a
        # connect/recv timeout (SidecarClient), while the server-side
        # reader idles between requests by design — its bound is peer
        # close.  The one shared recv in the tree, hence the suppression.
        # graftlint: disable=unbounded-socket-op
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise ConnectionError("socket closed mid-frame")
        buf.extend(chunk)
    return bytes(buf)
