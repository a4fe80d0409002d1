"""grafttrace: cross-layer span tracing + live metrics sampling.

The repo's perf and chaos claims used to rest on end-of-run aggregates
(LogParser scraping logs, one OP_STATS snapshot at teardown).  This
package makes every claim attributable to a *place in the pipeline*:

``spans``
    The span record schema and the buffered :class:`Tracer` the
    sidecar threads its hot path through: one span tree a request
    (request -> decode, queue, reply) and one a launch (pack, dispatch,
    device -> h2d, fetch_wait, d2h, bisect -> bisect_step), on one
    clock, ``t`` the END and ``t0`` the start.  Timestamps always come from the injected
    clock — graftlint's ``unclosed-span`` checker enforces both that
    and the begin/end pairing discipline.

``trace``
    The collector/merger: parses the C++ node's ``TRACE`` lines
    (proposal -> verify_submit -> verify_reply -> commit, keyed on
    block digest + round), estimates per-host clock offsets (RTT
    midpoint), stitches per-block commit traces across replica logs,
    computes the critical-path breakdown (p50/p99 per stage), and
    exports a Chrome-trace-event / Perfetto-loadable ``trace.json``.

``sampler``
    The live metrics sampler: polls OP_STATS at a fixed interval
    DURING the run window (not only at teardown), appending time-series
    samples to ``logs/metrics.jsonl`` so throughput/queue-wait over
    time can be plotted, chaos SLO verdicts can cite the recovery
    curve, and a chaos-killed sidecar's telemetry survives as the last
    good sample.  graftscope adds the C++ node's 1 Hz ``METRICS`` line
    reader: per-replica commit-rate/ingress/breaker series merged into
    the same artifact, plus straggler detection over them.

graftscope closes the attribution loop between the two halves: the
protocol-v5 context tag carries each block's digest through the verify
RPC, the sidecar tags its stage spans with it, and ``trace`` joins the
chains back onto the blocks — ``logs/trace.json`` nests device time
inside each block's verify segment, with ``join_rate`` saying what
fraction of verify-traced committed blocks carried a chain.
"""

from __future__ import annotations

from .sampler import (
    MetricsSampler,
    commit_rate_divergence,
    merge_node_series,
    parse_node_metrics,
    persistent_fetch,
    read_samples,
    recovery_curve,
    split_samples,
)
from .spans import SpanError, Tracer, parse_spans
from .trace import (
    build_run_trace,
    chain_spans,
    chrome_trace,
    clock_offset,
    critical_path,
    join_blocks,
    parse_node_trace,
    stitch_blocks,
    write_run_trace,
)

__all__ = [
    "MetricsSampler",
    "SpanError",
    "Tracer",
    "build_run_trace",
    "chain_spans",
    "chrome_trace",
    "clock_offset",
    "commit_rate_divergence",
    "critical_path",
    "join_blocks",
    "merge_node_series",
    "parse_node_metrics",
    "parse_node_trace",
    "parse_spans",
    "persistent_fetch",
    "read_samples",
    "recovery_curve",
    "split_samples",
    "stitch_blocks",
    "write_run_trace",
]
