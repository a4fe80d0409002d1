"""Request-class taxonomy for the verify scheduler.

Two classes exist on the wire and in the queues:

``LATENCY``
    QC/TC verifies from the consensus core (``OP_VERIFY_BATCH`` and every
    BLS verify/sign opcode).  HotStuff's responsiveness argument makes
    this the number that bounds commit latency: a replica cannot vote,
    and a leader cannot assemble the next block, until the previous
    certificate's signatures check out.  A latency request therefore
    never waits behind more than the launch already in flight.

``BULK``
    Mempool / offchain batch verifies (``OP_VERIFY_BULK``).  Throughput
    matters, per-request latency does not; bulk batches coalesce up to
    the bulk launch cap and yield to any pending latency work.

The mapping opcode -> class lives here (``class_of_opcode``) so the
connection handler, the scheduler, and the tests agree on one source of
truth.  Classes ride the wire as distinct opcodes rather than a header
flag: existing ``OP_VERIFY_BATCH`` clients keep their (correct)
latency-class behavior without a flag day, and the graftlint wire
cross-checker pins the opcode pair on both sides of the boundary.
"""

from __future__ import annotations

import threading
from time import monotonic

# Class identifiers (also the keys of every per-class stats dict).
LATENCY = "latency"
BULK = "bulk"

CLASSES = (LATENCY, BULK)


def class_of_opcode(opcode: int) -> str:
    """Wire opcode -> scheduling class (one source of truth)."""
    from .. import protocol as proto

    return BULK if opcode == proto.OP_VERIFY_BULK else LATENCY


class Pending:
    """One admitted request: the decoded dataclass, its reply callback,
    its class, its tenant (graftfleet: the third scheduling key; the
    connection's HELLO identity or the default), the admission
    timestamp (queue-wait telemetry), and — traced runs only — the
    request's span bookkeeping (``span``; opaque here, None untraced)."""

    __slots__ = ("request", "reply_fn", "cls", "enqueued_at", "is_bls",
                 "tenant", "span")

    def __init__(self, request, reply_fn, cls: str = LATENCY,
                 is_bls: bool = False, tenant: str | None = None,
                 span=None):
        from .tenantq import DEFAULT_TENANT

        self.request = request
        self.reply_fn = reply_fn
        self.cls = cls
        self.is_bls = is_bls
        self.span = span
        self.tenant = DEFAULT_TENANT if tenant is None else tenant
        self.enqueued_at = monotonic()

    def __len__(self):
        """Signature-record count (BLS requests schedule as one unit)."""
        if self.is_bls:
            return 1
        return len(self.request.msgs)


class Launch:
    """One assembled device launch: ordered items plus bookkeeping the
    engine thread needs to fan replies back out.

    ``kind`` is ``"verify"`` (a coalesced Ed25519 batch — possibly a
    latency batch padded out with bulk fill) or ``"bls"`` (a single BLS
    request, executed alone).  ``fill_count`` counts the trailing items
    that rode along as pad fill (telemetry only — replies are uniform).
    """

    __slots__ = ("kind", "items", "cls", "fill_count", "assembled_at")

    def __init__(self, kind: str, items: list, cls: str,
                 fill_count: int = 0):
        self.kind = kind
        self.items = items
        self.cls = cls
        self.fill_count = fill_count
        self.assembled_at = monotonic()

    @property
    def total_sigs(self) -> int:
        return sum(len(p) for p in self.items)


class ClassQueue:
    """Bounded queue for one class, counted in signature records, with
    per-tenant lanes (graftfleet) drained in deficit round-robin order.

    ``offer`` is called from connection threads and never blocks: a full
    queue returns False and the caller replies queue-full immediately —
    the bounded-backpressure contract that keeps a flooded sidecar from
    wedging every connection thread behind one blocking ``put``.  The
    engine thread is the only consumer.  A lock (shared with the
    scheduler, which needs cross-queue atomicity when assembling) guards
    the lanes + the signature count.

    Two caps govern admission: the CLASS cap (total records queued, as
    before) and the per-TENANT cap — one tenant's lane may hold at most
    ``tenant_cap_sigs`` records, so a flooding tenant saturates its own
    share and sheds while every other tenant keeps admitting.  A single
    tenant (the pre-fleet topology) therefore sees exactly the old
    behavior when its cap equals the class cap.  ``last_refusal``
    records why the most recent ``_offer_locked`` said no
    (``"tenant-cap"`` vs ``"class-cap"``), valid until the lock is
    released — the scheduler reads it to attribute sheds for the
    tenant-starvation invariant.
    """

    __slots__ = ("lanes", "cap_sigs", "tenant_cap_sigs", "last_refusal",
                 "_lock")

    def __init__(self, cap_sigs: int, lock: threading.Condition,
                 tenant_cap_sigs: int | None = None,
                 quantum_sigs: int | None = None):
        from .tenantq import DRR_QUANTUM_SIGS, TenantLanes

        self.lanes = TenantLanes(
            DRR_QUANTUM_SIGS if quantum_sigs is None else quantum_sigs)
        self.cap_sigs = cap_sigs
        self.tenant_cap_sigs = cap_sigs if tenant_cap_sigs is None \
            else min(tenant_cap_sigs, cap_sigs)
        self.last_refusal = None
        self._lock = lock

    @property
    def sigs(self) -> int:
        """Total queued signature records (the lanes own the count)."""
        return self.lanes.sigs

    def offer(self, pending: Pending) -> bool:
        with self._lock:
            return self._offer_locked(pending)

    def _offer_locked(self, pending: Pending, cap_sigs: int | None = None)\
            -> bool:
        # A request is admitted whole or not at all; a single request
        # bigger than the whole cap is still admitted when the queue
        # (respectively its own lane) is empty — it slices inside the
        # engine — so a legal client can never be starved by its own
        # size.  ``cap_sigs`` lets the scheduler admit against a DERATED
        # cap (graftsurge) without the queue itself knowing about
        # admission policy.  The TENANT share is checked first: a
        # flooding tenant must shed on its own cap while the class still
        # has room for everyone else.
        self.last_refusal = None
        cap = self.cap_sigs if cap_sigs is None else cap_sigs
        lane_sigs = self.lanes.tenant_sigs_locked(pending.tenant)
        # The tenant share engages only once a SECOND tenant has been
        # seen: with one tenant (the pre-fleet topology) the class cap
        # is the whole policy and behavior is byte-identical to v5.
        multi_tenant = len(self.lanes.lanes) >= 2 or (
            self.lanes.lanes and pending.tenant not in self.lanes.lanes)
        tenant_cap = min(self.tenant_cap_sigs, cap)
        if multi_tenant and lane_sigs and \
                lane_sigs + len(pending) > tenant_cap:
            self.last_refusal = "tenant-cap"
            return False
        if self.lanes.sigs and self.lanes.sigs + len(pending) > cap:
            self.last_refusal = "class-cap"
            return False
        self.lanes._offer_locked(pending)
        self._lock.notify()
        return True

    def _head_locked(self) -> Pending | None:
        """The DRR-selected next item (None when empty) — the only legal
        way to inspect drain order; raw lane access bypasses the tenant
        key (graftlint: tenant-unscoped-queue)."""
        return self.lanes.head_locked()

    def _pop_locked(self) -> Pending:
        return self.lanes.pop_next_locked()

    def __bool__(self):
        return bool(self.lanes)

    def __len__(self):
        return len(self.lanes)
