"""Env-gated telemetry for the device hot path — spans, counters,
histograms, and two exporters.

The observability backbone the ROADMAP's perf items read: every open
question there (compile-vs-execute split of the 81s attestation
first-call, the `_MSM_DEVICE_MIN=16` host/device break-even, bucket
padding waste) is answered from this registry rather than a single
end-to-end number — the decomposition-first methodology of the
committee-signature measurement literature
(arXiv:2302.00418, arXiv:2602.06655).

Gates (all collection OFF by default, disabled paths are a flag check
and, for spans, one check of the profiler's state):

    CST_TELEMETRY=1       collect spans/counters/histograms in-process
    CST_TRACE_FILE=f.json also write a Chrome trace-event file at exit
                          (Perfetto / chrome://tracing loadable)

Surface:

    span(name, **attrs)   nestable wall-clock section (ctx manager);
                          while a JAX profiler session records it is a
                          jax.profiler.TraceAnnotation `cst.<name>`,
                          registry on or off, so the same names appear
                          in the profiler's trace beside the device
    profiled_spans()      count and total of the spans closed while a
                          profiler session recorded
    count(name, n=1)      monotonic counter
    observe(name, v)      histogram sample (count/total/min/max)
    gauge(name, v)        level sample (serve queue depth, in-flight
                          batches): can go down, and each sample is a
                          Chrome-trace 'C' counter event
    set_meta(k, v)        one-shot string/num metadata (cache dir, ...)
    add_event(name, dur)  record an externally-measured duration as a
                          closed span (derived phase accounting)
    span_seconds(name)    one span's cumulative total_s — point read
    first_call(key)       True once per key — compile-vs-run attribution
    snapshot()            the whole registry as a dict (stable schema)
    reset(), configure(), enabled()
    write_jsonl(path), write_chrome_trace(path), chrome_trace()

Cost model (`costmodel` submodule, gated CST_TELEMETRY + CST_COSTMODEL):
per-kernel XLA cost/memory analysis (`costmodel.capture`), roofline
utilization + compute/memory/launch-bound classification against the
per-backend peak registry (`costmodel.block`), and per-device live-
buffer watermarks sampled at span boundaries
(`costmodel.sample_watermark`).  Flows into `snapshot()["costmodel"]`
and the Chrome trace ('C' counter events).

Live monitoring (`metrics_export` + `monitor` submodules): a zero-dep
Prometheus text-exposition endpoint (`CST_METRICS_PORT`) publishing the
registry/reqtrace/costmodel/serve-status surfaces per scrape, and the
declarative SLO watchdog (`CST_SLO_RULES` rules, rolling windows,
breach→clear hysteresis, typed `SloBreach` events with worst-N reqtrace
exemplars and an optional `CST_PROFILE_ON_BREACH` profiler grab).  The
watchdog's round summary rides the serve block (`"slo"` sub-object,
`validate_slo_block`).

Occupancy + flight recorder (`occupancy` + `flightrec` submodules):
the per-device busy/bubble interval ledger (`CST_OCCUPANCY`) that
attributes every idle gap in the serve pipeline to {host_prep,
queue_starved, settle_serialized, drain} and scores how much host prep
hid under device wall (the serve block's `"occupancy"` sub-object,
per-device Chrome busy tracks, `cst_serve_device_busy_frac`
exposition), and the bounded cross-stack incident event ring whose
`dump_bundle()` freezes breaker/fault/mesh/SLO/occupancy evidence into
one self-contained directory on watchdog breach, poison storm, or
`python -m consensus_specs_tpu.telemetry.flightrec`.

Zero dependencies (stdlib only); never imports jax, numpy, or any spec
module — safe to import from anywhere, including before backend pinning.
"""

from . import costmodel, flightrec, metrics_export, monitor, occupancy, reqtrace
from .core import (
    add_event,
    configure,
    count,
    counter_value,
    enabled,
    first_call,
    gauge,
    observe,
    profiled_spans,
    reset,
    set_meta,
    snapshot,
    span,
    span_seconds,
)
from .export import (
    chrome_trace,
    validate_checkpoint_block,
    validate_costmodel_block,
    validate_latency_attribution,
    validate_mesh_block,
    validate_occupancy_block,
    validate_resilience_block,
    validate_serve_block,
    validate_slo_block,
    write_chrome_trace,
    write_jsonl,
)

__all__ = [
    "add_event", "configure", "costmodel", "count", "counter_value",
    "enabled", "first_call", "flightrec", "gauge", "metrics_export",
    "monitor", "observe", "occupancy", "profiled_spans", "reqtrace",
    "reset",
    "set_meta",
    "snapshot", "span", "span_seconds", "chrome_trace",
    "validate_checkpoint_block", "validate_costmodel_block",
    "validate_latency_attribution",
    "validate_mesh_block", "validate_occupancy_block",
    "validate_resilience_block",
    "validate_serve_block", "validate_slo_block",
    "write_chrome_trace", "write_jsonl",
]
