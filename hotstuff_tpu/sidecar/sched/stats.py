"""Per-launch telemetry for the verify scheduler.

Counters answer the questions the drain-loop engine could not: how big
are launches actually (coalesce histogram), how many tenants' requests
one launch holds (tenants histogram), how much padded capacity is
wasted (pad_waste vs bulk fill), which verify path ran (per_sig / rlc /
rlc_bisect / host / rlc_sharded / ladder_sharded), how long requests sat
queued per class (p50/p99), how often backpressure fired, how mesh
launches distribute over per-shard buckets, how many bulk backlogs
drained as ONE whole-backlog chunked scan instead of per-launch_cap
slices (the ``scan`` section), and how much of the host pack work the
double-buffered dispatch pipeline actually hid behind device execution
(the ``pipeline`` overlap ratio) and how many launches it dispatched
while the launch before was still in flight (``dispatch_ahead_share``).

Exposed over the wire as the ``OP_STATS`` reply (one JSON object — the
snapshot() dict verbatim), which the harness fetches at teardown into
the LogParser summary and bench.py folds into the headline line.

Writers: the engine thread (launch/path/wait counters) and connection
threads (queue_full rejections, admissions).  One lock guards it all —
every operation is a few integer bumps, invisible next to a device
launch.
"""

from __future__ import annotations

import threading
from time import monotonic

# Rolling window for the ``pipeline`` overlap section: bounded both by
# entry count and by age.  Lifetime totals once lived here — on a
# long-running sidecar they dampened the overlap ratio exactly when a
# surge arrived (hours of healthy history outvoting the collapse in
# front of it), which also starved the surge controller's derate.  The
# window matches the admission controller's recency discipline
# (surge.PACK_WINDOW_S); lifetime totals stay visible under
# ``lifetime_*`` keys for trend tooling.
PIPE_WINDOW = 512
PIPE_WINDOW_S = 30.0


def _percentile(sorted_vals: list, q: float) -> float:
    """Nearest-rank percentile of an ascending list (empty -> 0)."""
    if not sorted_vals:
        return 0.0
    idx = max(0, min(len(sorted_vals) - 1,
                     int(round(q * (len(sorted_vals) - 1)))))
    return sorted_vals[idx]


class SchedStats:
    # Bounded queue-wait reservoirs per class: enough resolution for a
    # p99 over a bench window, bounded so a week-long sidecar cannot
    # grow without limit (newest samples win — the interesting tail).
    WAIT_SAMPLES_CAP = 4096

    # graftfleet: distinct tenants tracked in the per-tenant section.
    # A fleet serves committees, not the open internet — 64 is an order
    # of magnitude past any plausible local deployment, and the bound
    # keeps a tenant-id fuzzer from growing the stats dict without
    # limit (overflow tenants fold into "~other").
    TENANT_STATS_CAP = 64
    TENANT_WAIT_SAMPLES_CAP = 1024
    OVERFLOW_TENANT = "~other"

    def __init__(self, clock=monotonic):
        from collections import deque

        self._lock = threading.Lock()
        self._clock = clock
        self.launches = 0
        self.launches_by_class: dict[str, int] = {}
        # coalesce-size histogram: padded-bucket capacity -> launches
        self.coalesce_hist: dict[int, int] = {}
        # distinct tenants a launch holds -> launches: how far the lanes
        # of a shared sidecar coalesce into one device program
        self.tenants_hist: dict[int, int] = {}
        self.sigs_launched = 0
        self.pad_waste_sigs = 0          # padded slots left empty
        self.bulk_fill_sigs = 0          # padded slots used by bulk fill
        self.paths: dict[str, int] = {}  # per_sig / rlc / rlc_bisect / ...
        # What failed combined checks cost (crypto/eddsa.py
        # verify_batch_rlc_pack's fetch): batches resolved (what
        # paths.rlc_bisect counts), device programs the resolutions ran
        # (one a batch), rows they verified per signature (a batch's
        # canonical rows), rows found false.
        self.bisect = {"batches": 0, "programs": 0, "rows_per_sig": 0,
                       "bad_rows": 0}
        # BLS verify requests the engine took, by kind (votes / agg /
        # multi); Miller loops the device pairing program ran (2 a
        # common-message certificate, n+1 a multi-digest one); requests
        # rejected before any pairing (decode, subgroup, identity).
        # Where each verdict came from is in ``paths`` (bls_pairing,
        # host) and ``dedup.cache_hits``.
        self.bls = {"requests": {}, "pairings": 0, "decode_rejects": 0}
        self.admitted: dict[str, int] = {}
        self.queue_full: dict[str, int] = {}
        self.carries: dict[str, int] = {}
        # Mesh routing: launches that went to the device mesh, and the
        # per-SHARD padded bucket each landed on (the warmed-shape
        # discipline made visible: every key here must be a bucket the
        # warmup marked, or a cold compile happened mid-traffic).
        self.mesh_launches = 0
        self.shard_bucket_hist: dict[int, int] = {}
        # graftingress bulk-lane class mix: OP_VERIFY_BULK requests are
        # fed by the mempool admission-verify stage (request ctx ==
        # the pinned ingress tag) or by offchain batches; the split is
        # what makes "bulk-lane utilization under signed ingress" a
        # number instead of a guess.
        self.ingress_bulk_requests = 0
        self.ingress_bulk_sigs = 0
        self.offchain_bulk_requests = 0
        self.offchain_bulk_sigs = 0
        # graftscale whole-backlog scans: backlogs drained as ONE
        # chunked mesh program instead of per-launch_cap ladder slices.
        # chunk_hist keys are the scan chunk counts g — like the shard
        # buckets, every key must be a g the warmup marked
        # (ShapeRegistry.mesh_chunks) or a cold compile happened.
        self.scan_launches = 0
        self.scan_sigs = 0
        self.scan_chunk_hist: dict[int, int] = {}
        self.scan_slices_avoided = 0
        # Double-buffered dispatch pipeline: host pack time and the
        # share of it that ran while a launch was already executing on
        # the device (hidden == free; the overlap ratio is the pipeline
        # doing its job).  The reported section is computed over the
        # bounded rolling window; the lifetime accumulators survive for
        # trend tooling only.
        self.pack_s = 0.0
        self.pack_hidden_s = 0.0
        self._pack_window = deque(maxlen=PIPE_WINDOW)  # (t, dur, hidden)
        # Launches the staged engine put on the device, and whether each
        # went while another launch was still in flight (dispatched
        # ahead of that launch's drain): the same rolling window.
        self._dispatch_window = deque(maxlen=PIPE_WINDOW)  # (t, ahead)
        self._waits = {c: deque(maxlen=self.WAIT_SAMPLES_CAP)
                       for c in ("latency", "bulk")}
        # graftfleet per-tenant section: admissions/sheds per class and
        # a bounded queue-wait reservoir per (tenant, class) — the
        # numbers the fairness invariant is judged on (a victim tenant's
        # latency p99 under a neighboring flood).  Bounded by
        # TENANT_STATS_CAP distinct tenants; see _tenant_locked.
        self._tenants: dict[str, dict] = {}
        # graftsurge: the admission controller (sched/surge.py), attached
        # by the Scheduler.  note_pack/note_launch forward the engine's
        # observations into it (outside this object's lock — the nesting
        # is always stats-caller -> surge lock, never back), and
        # snapshot() folds its counters in as the ``surge`` section.
        self.surge = None

    # -- recording ----------------------------------------------------------

    def _tenant_locked(self, tenant: str) -> dict:
        """The per-tenant record, creating it under the cap (overflow
        tenants share one "~other" bucket so the dict stays bounded)."""
        from collections import deque

        rec = self._tenants.get(tenant)
        if rec is None:
            if len(self._tenants) >= self.TENANT_STATS_CAP:
                tenant = self.OVERFLOW_TENANT
                rec = self._tenants.get(tenant)
            if rec is None:
                rec = self._tenants[tenant] = {
                    "admitted": {},
                    "shed": {},
                    "waits": {c: deque(
                        maxlen=self.TENANT_WAIT_SAMPLES_CAP)
                        for c in ("latency", "bulk")},
                }
        return rec

    def note_tenant_admitted(self, tenant: str, cls: str):
        with self._lock:
            adm = self._tenant_locked(tenant)["admitted"]
            adm[cls] = adm.get(cls, 0) + 1

    def note_tenant_shed(self, tenant: str, cls: str):
        with self._lock:
            shed = self._tenant_locked(tenant)["shed"]
            shed[cls] = shed.get(cls, 0) + 1

    def note_admitted(self, cls: str):
        with self._lock:
            self.admitted[cls] = self.admitted.get(cls, 0) + 1

    def note_queue_full(self, cls: str):
        with self._lock:
            self.queue_full[cls] = self.queue_full.get(cls, 0) + 1

    def note_carry(self, cls: str):
        with self._lock:
            self.carries[cls] = self.carries.get(cls, 0) + 1

    def note_launch(self, launch, capacity: int, now: float):
        """One assembled launch: size/pad/fill accounting + queue waits.
        ``capacity`` is the padded device shape the batch rides in."""
        if self.surge is not None:
            self.surge.note_launch(launch.total_sigs, now)
        with self._lock:
            self.launches += 1
            self.launches_by_class[launch.cls] = \
                self.launches_by_class.get(launch.cls, 0) + 1
            total = launch.total_sigs
            self.sigs_launched += total
            self.coalesce_hist[capacity] = \
                self.coalesce_hist.get(capacity, 0) + 1
            self.pad_waste_sigs += max(0, capacity - total)
            fill = launch.items[len(launch.items) - launch.fill_count:]
            self.bulk_fill_sigs += sum(len(p) for p in fill)
            tenants = set()
            for p in launch.items:
                waits = self._waits.get(p.cls)
                if waits is not None:
                    waits.append(now - p.enqueued_at)
                tenant = getattr(p, "tenant", None) or "default"
                tenants.add(tenant)
                tw = self._tenant_locked(tenant)["waits"]
                if p.cls in tw:
                    tw[p.cls].append(now - p.enqueued_at)
            self.tenants_hist[len(tenants)] = \
                self.tenants_hist.get(len(tenants), 0) + 1

    def note_bulk_source(self, ingress: bool, sigs: int):
        """One offered bulk-lane request, split by feed: ingress-fed
        (mempool admission verify, pinned ctx tag) vs offchain-fed.
        Counted at submit time — offered load, not admitted load — so
        the mix stays honest under backpressure."""
        with self._lock:
            if ingress:
                self.ingress_bulk_requests += 1
                self.ingress_bulk_sigs += sigs
            else:
                self.offchain_bulk_requests += 1
                self.offchain_bulk_sigs += sigs

    def note_path(self, path: str):
        with self._lock:
            self.paths[path] = self.paths.get(path, 0) + 1

    def note_bls_request(self, kind: str):
        with self._lock:
            reqs = self.bls["requests"]
            reqs[kind] = reqs.get(kind, 0) + 1

    def note_bls_pairings(self, pairings: int):
        with self._lock:
            self.bls["pairings"] += pairings

    def note_bls_decode_reject(self):
        with self._lock:
            self.bls["decode_rejects"] += 1

    def note_bisect(self):
        """One batch whose combined check failed: ``paths.rlc_bisect``
        and ``bisect.batches`` together."""
        with self._lock:
            self.paths["rlc_bisect"] = self.paths.get("rlc_bisect", 0) + 1
            self.bisect["batches"] += 1

    def note_bisect_resolved(self, programs: int, rows_per_sig: int,
                             bad_rows: int):
        """The bisection of one batch is complete (single-chip route;
        the mesh's resolution reports no totals)."""
        with self._lock:
            self.bisect["programs"] += programs
            self.bisect["rows_per_sig"] += rows_per_sig
            self.bisect["bad_rows"] += bad_rows

    def note_mesh_launch(self, buckets):
        """One scheduler launch dispatched onto the mesh: counted ONCE,
        with every per-slice shard bucket recorded in the histogram.
        ``buckets`` is the list of per-shard padded buckets the launch's
        ladder slices landed on (one entry for an unsliced launch; None
        entries — a registry without a mesh size — are counted but not
        bucketed).  The old shape called this per SLICE, so a sliced
        backlog inflated ``sharded_launches`` past the scheduler's own
        launch count and the two could never be compared."""
        with self._lock:
            self.mesh_launches += 1
            for b in buckets:
                if b is not None:
                    self.shard_bucket_hist[b] = \
                        self.shard_bucket_hist.get(b, 0) + 1

    def note_scan_launch(self, g: int, sigs: int, slices_avoided: int):
        """One whole-backlog chunked mesh scan launch: g chunks drained
        ``sigs`` signatures in ONE dispatch; ``slices_avoided`` is how
        many extra per-launch_cap ladder dispatches the pre-graftscale
        path would have paid for the same backlog."""
        with self._lock:
            self.scan_launches += 1
            self.scan_sigs += sigs
            self.scan_chunk_hist[g] = self.scan_chunk_hist.get(g, 0) + 1
            self.scan_slices_avoided += max(0, slices_avoided)

    def note_pack(self, duration_s: float, hidden: bool,
                  now: float | None = None):
        """One host-side pack stage: ``hidden`` says a launch was
        executing on the device when the pack began, i.e. the pipeline
        overlapped this pack with device compute (the approximation is
        conservative per-launch and exact in the steady state, where
        pack N+1 runs entirely under launch N)."""
        now = self._clock() if now is None else now
        if self.surge is not None:
            self.surge.note_pack(duration_s, hidden, now=now)
        with self._lock:
            self.pack_s += duration_s
            if hidden:
                self.pack_hidden_s += duration_s
            self._pack_window.append((now, duration_s, bool(hidden)))

    def note_dispatch(self, ahead: int, now: float | None = None):
        """One launch dispatched by the staged engine; ``ahead`` is the
        launches still in flight at that moment (> 0: dispatched before
        the launch before it was drained)."""
        now = self._clock() if now is None else now
        with self._lock:
            self._dispatch_window.append((now, ahead > 0))

    # -- reporting ----------------------------------------------------------

    def _pipeline_locked(self) -> dict:
        """The ``pipeline`` section over the bounded rolling window —
        the same keys the LogParser and the surge derate have always
        read, now answering for RECENT pack-boundedness; lifetime
        accumulators ride along under ``lifetime_*``."""
        now = self._clock()
        for window in (self._pack_window, self._dispatch_window):
            while window and now - window[0][0] > PIPE_WINDOW_S:
                window.popleft()
        win = sum(d for _, d, _ in self._pack_window)
        win_hidden = sum(d for _, d, h in self._pack_window if h)
        dispatches = len(self._dispatch_window)
        ahead = sum(1 for _, a in self._dispatch_window if a)
        return {
            "pack_ms": round(win * 1e3, 3),
            "pack_hidden_ms": round(win_hidden * 1e3, 3),
            "overlap_ratio": round(win_hidden / win, 3) if win else 0.0,
            "dispatches": dispatches,
            "dispatch_ahead": ahead,
            "dispatch_ahead_share": round(ahead / dispatches, 3)
            if dispatches else 0.0,
            "window_s": PIPE_WINDOW_S,
            "lifetime_pack_ms": round(self.pack_s * 1e3, 3),
            "lifetime_overlap_ratio": round(
                self.pack_hidden_s / self.pack_s, 3)
            if self.pack_s else 0.0,
        }

    def snapshot(self) -> dict:
        """JSON-safe dict: the OP_STATS reply body, byte-for-byte."""
        surge = self.surge.snapshot() if self.surge is not None else None
        with self._lock:
            waits = {}
            for cls, samples in self._waits.items():
                vals = sorted(samples)
                waits[cls] = {
                    "n": len(vals),
                    "p50_ms": round(_percentile(vals, 0.50) * 1e3, 3),
                    "p99_ms": round(_percentile(vals, 0.99) * 1e3, 3),
                }
            out = {
                "launches": self.launches,
                "launches_by_class": dict(self.launches_by_class),
                "coalesce_hist": {str(k): v for k, v in
                                  sorted(self.coalesce_hist.items())},
                "tenants_hist": {str(k): v for k, v in
                                 sorted(self.tenants_hist.items())},
                "sigs_launched": self.sigs_launched,
                "pad_waste_sigs": self.pad_waste_sigs,
                "bulk_fill_sigs": self.bulk_fill_sigs,
                "paths": dict(self.paths),
                "bisect": dict(self.bisect),
                "bls": dict(self.bls, requests=dict(self.bls["requests"])),
                "admitted": dict(self.admitted),
                "queue_full": dict(self.queue_full),
                "carries": dict(self.carries),
                "queue_wait": waits,
                "mesh": {
                    "sharded_launches": self.mesh_launches,
                    "shard_buckets": {
                        str(k): v for k, v in
                        sorted(self.shard_bucket_hist.items())},
                },
                "scan": {
                    "launches": self.scan_launches,
                    "sigs": self.scan_sigs,
                    "chunk_hist": {
                        str(k): v for k, v in
                        sorted(self.scan_chunk_hist.items())},
                    "slices_avoided": self.scan_slices_avoided,
                },
                "pipeline": self._pipeline_locked(),
                "tenants": {
                    tenant: {
                        "admitted": dict(rec["admitted"]),
                        "shed": dict(rec["shed"]),
                        "queue_wait": {
                            cls: {
                                "n": len(v),
                                "p50_ms": round(
                                    _percentile(v, 0.50) * 1e3, 3),
                                "p99_ms": round(
                                    _percentile(v, 0.99) * 1e3, 3),
                            }
                            for cls, samples in rec["waits"].items()
                            if (v := sorted(samples))
                        },
                    }
                    for tenant, rec in sorted(self._tenants.items())
                },
                "ingress": {
                    "bulk_requests": self.ingress_bulk_requests,
                    "bulk_sigs": self.ingress_bulk_sigs,
                    "offchain_requests": self.offchain_bulk_requests,
                    "offchain_sigs": self.offchain_bulk_sigs,
                },
            }
            if surge is not None:
                out["surge"] = surge
            return out
