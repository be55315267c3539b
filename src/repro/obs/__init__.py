"""repro.obs — serving observability: metrics, SLO summaries, timelines,
and wall-clock spans.

Host-side only, by construction: nothing in this package reads a device
array, so observing a serve adds ZERO dispatches and ZERO host syncs.
``metrics`` and ``timeline`` consume the event dicts a
``trace.TraceRecorder`` emits (live, via ``sinks=``) or a recorded
``trace.Trace`` (offline), on the engine-tick clock. ``spans`` is the one
module the engine itself calls: it times each step's phases on the host
clock and notes GC pauses and compiles per step. The ``repro.verify``
host-sync AST lint scans this package along with serve/sched, and the
zero-overhead tests pin dispatch/host-sync counts metrics-on vs
metrics-off and profiler-on vs profiler-off for every policy.

  metrics   ``MetricsHub``: counter/gauge/histogram registry, per-request
            lifecycle timelines (arrival -> admit -> prefill chunks ->
            first token -> per-token decode -> completion), and the derived
            SLO summary (p50/p95/p99 TTFT & TPOT in engine-clock ticks,
            queue depth, slot occupancy, valid-token fraction, dispatch
            mix) — JSON-serializable.
  timeline  Chrome/Perfetto trace-event export: dispatch spans (fused
            pairs as one slice, supersteps as nested round slices),
            async-fetch flows, per-slot request lanes, queue-depth
            counters, and simulator-replay NPU/PIM stream spans, into one
            ``trace.json``.
  spans     ``span(log, name)``: a profiler annotation plus a self time in
            the engine's ``StepLog``, a ring of per-step records (host time
            by ``serve.*`` phase, GC pauses, backend compiles);
            ``spans.latest()`` reaches the newest engine's log.

CLI: ``python -m repro.launch.stats <trace.jsonl>`` emits the metrics
report and timeline for any recorded trace;
``benchmarks/latency_guard.py`` holds p50/p99 TTFT/TPOT to a committed
baseline in CI.
"""
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsHub,
                               PERCENTILES, RequestLifecycle)
from repro.obs.timeline import (NODE_PID_STRIDE, PID_ENGINE, PID_FLEET,
                                PID_SIM, PID_SLOTS, TICK_US, dispatch_slices,
                                engine_events, fleet_events, fleet_node_pids,
                                sim_events, write_chrome_trace)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsHub", "PERCENTILES",
    "RequestLifecycle",
    "NODE_PID_STRIDE", "PID_ENGINE", "PID_FLEET", "PID_SIM", "PID_SLOTS",
    "TICK_US", "dispatch_slices", "engine_events", "fleet_events",
    "fleet_node_pids", "sim_events", "write_chrome_trace",
]
