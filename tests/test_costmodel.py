"""The kernel cost model (`consensus_specs_tpu/telemetry/costmodel.py`):
XLA cost/memory capture on a jitted toy kernel (exact flops for a known
matmul), peak-registry classification boundaries, watermark high-water
monotonicity, the snapshot and block schemas, and the measured no-op
bound when CST_COSTMODEL is off."""

import json
import time

import pytest

from consensus_specs_tpu import telemetry
from consensus_specs_tpu.telemetry import costmodel
from consensus_specs_tpu.telemetry import core as tcore


@pytest.fixture(autouse=True)
def _isolated_registry():
    """Enable telemetry+costmodel against a saved/restored registry so
    a CST_TELEMETRY CI session keeps its session-wide data."""
    state = tcore._save_state()
    cm_state = (dict(costmodel._costs), dict(costmodel._watermarks),
                list(costmodel._wm_events), costmodel._wm_events_dropped)
    prev_enabled = telemetry.enabled()
    telemetry.configure(enabled=True)
    costmodel.configure(enabled=True)
    tcore.reset(full=True)
    yield
    telemetry.configure(enabled=prev_enabled)
    costmodel.configure(enabled=None)
    tcore._restore_state(state)
    with costmodel._lock:
        costmodel._costs.clear()
        costmodel._costs.update(cm_state[0])
        costmodel._watermarks.clear()
        costmodel._watermarks.update(cm_state[1])
        costmodel._wm_events.clear()
        costmodel._wm_events.extend(cm_state[2])
        costmodel._wm_events_dropped = cm_state[3]


# --- capture ----------------------------------------------------------------


def _toy_matmul():
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda a, b: a @ b)
    x = jnp.ones((8, 8), jnp.float32)
    return f, x


def test_capture_exact_flops_for_known_matmul():
    f, x = _toy_matmul()
    f(x, x)
    rec = costmodel.capture("toy_matmul@8", f, (x, x))
    # 8x8x8 matmul: 2*M*N*K = 1024 flops, 3 x 256-byte buffers touched
    assert rec["flops"] == 1024.0
    assert rec["bytes_accessed"] == 768.0
    assert rec["platform"] == "cpu"
    assert rec["run_s_probe"] > 0
    mem = rec.get("memory")
    if mem is not None:   # backend-dependent; exact when present
        assert mem["argument_size_in_bytes"] == 512
        assert mem["output_size_in_bytes"] == 256


def test_capture_is_once_per_kernel_key():
    f, x = _toy_matmul()
    rec1 = costmodel.capture("once@8", f, (x, x))
    rec2 = costmodel.capture("once@8", f, (x, x))
    assert rec1 is not None and rec2 == rec1
    assert telemetry.snapshot()["costmodel"]["kernels"]["once@8"][
        "flops"] == 1024.0


def test_capture_failure_stores_error_record_never_raises():
    rec = costmodel.capture("broken@1", object(), (1,))
    assert "error" in rec and rec["kernel"] == "broken@1"
    assert telemetry.snapshot()["counters"][
        "costmodel.capture_errors"] == 1
    # an error record is still schema-valid inside the block
    assert telemetry.validate_costmodel_block(costmodel.block()) == []


def test_record_cost_direct_injection():
    costmodel.record_cost("synthetic@4", flops=100.0,
                          bytes_accessed=10.0, platform="tpu",
                          device_kind="TPU v5 lite", run_s_probe=1.0)
    blk = costmodel.block()
    rec = blk["kernels"]["synthetic@4"]
    assert rec["bound"] in ("compute", "memory", "launch")
    assert rec["peak_source"] == "TPU v5 lite"


# --- classification boundaries ----------------------------------------------


PEAK = {"flops_per_s": 100.0, "bytes_per_s": 10.0}


def test_classify_compute_bound():
    out = costmodel.classify(flops=100.0, bytes_accessed=1.0,
                             run_s=1.0, peak=PEAK)
    # t_compute = 1.0 >= t_memory = 0.1, and not launch
    assert out["bound"] == "compute"
    assert out["util_flops_pct"] == 100.0
    assert out["arithmetic_intensity"] == 100.0


def test_classify_memory_bound():
    out = costmodel.classify(flops=1.0, bytes_accessed=10.0,
                             run_s=1.0, peak=PEAK)
    # t_memory = 1.0 > t_compute = 0.01
    assert out["bound"] == "memory"
    assert out["util_bw_pct"] == 100.0


def test_classify_launch_bound():
    # both roofline legs explain < LAUNCH_BOUND_FRAC of the wall
    out = costmodel.classify(flops=1.0, bytes_accessed=1.0,
                             run_s=100.0, peak=PEAK)
    assert out["bound"] == "launch"


def test_classify_launch_boundary_is_exclusive():
    # exactly at the threshold: max leg == LAUNCH_BOUND_FRAC * run_s is
    # NOT launch-bound (strictly-less-than semantics)
    run_s = 1.0
    t_leg = costmodel.LAUNCH_BOUND_FRAC * run_s
    out = costmodel.classify(flops=PEAK["flops_per_s"] * t_leg,
                             bytes_accessed=0.0, run_s=run_s, peak=PEAK)
    assert out["bound"] == "compute"


def test_classify_without_peak_or_run_is_unknown():
    assert costmodel.classify(1.0, 1.0, None, PEAK)["bound"] == "unknown"
    assert costmodel.classify(1.0, 1.0, 1.0, None)["bound"] == "unknown"


def test_peaks_registry_reads_baseline_json():
    reg = costmodel.peaks()
    assert reg["TPU v5 lite"]["flops_per_s"] > 0
    assert "Google Cloud" in reg["TPU v5 lite"]["source"]
    assert reg["cpu"]["advisory"] is True
    entry = costmodel.peaks_for("TPU v5 lite")
    assert entry and entry["backend"] == "TPU v5 lite"
    assert costmodel.peaks_for("cpu")["advisory"] is True


@pytest.mark.parametrize("kind", ["TPU v6 lite", "TPU v4", "tpu", "quantum"])
def test_peaks_for_unknown_device_kind_is_none_and_warns(kind, capsys):
    # no other chip's row stands in: a v5e prefix match would price a
    # different generation's kernels against the v5e roofline
    costmodel._warned_kinds.discard(kind)
    assert costmodel.peaks_for(kind) is None
    assert kind in capsys.readouterr().err
    costmodel.record_cost("unknown@1", flops=1.0, bytes_accessed=1.0,
                          platform="tpu", device_kind=kind,
                          run_s_probe=1.0)
    rec = costmodel.block()["kernels"]["unknown@1"]
    assert rec["bound"] == "unknown" and "peak_source" not in rec


# --- watermarks -------------------------------------------------------------


def test_watermark_high_water_is_monotone():
    import jax.numpy as jnp

    keep = [jnp.ones((1024,), jnp.float32)]
    costmodel.sample_watermark("t0")
    keep.append(jnp.ones((2048,), jnp.float32))
    costmodel.sample_watermark("t1")
    keep.append(jnp.ones((4096,), jnp.float32))
    costmodel.sample_watermark("t2")
    wms = costmodel.raw_snapshot()["watermarks"]
    assert wms, "no watermark devices sampled"
    for dev, wm in wms.items():
        assert wm["high_water_bytes"] >= wm["last_bytes"]
        assert wm["samples"] >= 3
    # high water never decreases even after buffers are freed
    high = {d: w["high_water_bytes"] for d, w in wms.items()}
    del keep
    costmodel.sample_watermark("t3")
    for dev, wm in costmodel.raw_snapshot()["watermarks"].items():
        assert wm["high_water_bytes"] >= high[dev]


def test_watermark_counter_events_in_chrome_trace():
    import jax.numpy as jnp

    _ = jnp.ones((16,), jnp.float32)
    costmodel.sample_watermark("phase")
    f, x = _toy_matmul()
    costmodel.capture("traced@8", f, (x, x))
    trace = telemetry.chrome_trace()
    counters = [e for e in trace["traceEvents"] if e["ph"] == "C"]
    names = {e["name"] for e in counters}
    assert "device_memory_bytes" in names
    assert "cost.traced@8" in names
    mem = [e for e in counters if e["name"] == "device_memory_bytes"]
    assert all(isinstance(v, int) and v >= 0
               for e in mem for v in e["args"].values())
    json.dumps(trace)


# --- snapshot / block schemas ----------------------------------------------


def test_block_joins_dispatch_run_hist_over_probe():
    costmodel.record_cost("joined@8", flops=10.0, bytes_accessed=10.0,
                          run_s_probe=9.9)
    telemetry.observe("kernel.joined@8.run_s", 0.5)
    telemetry.observe("kernel.joined@8.run_s", 1.5)
    rec = costmodel.block()["kernels"]["joined@8"]
    assert rec["run_s_mean"] == 1.0          # hist mean, not the probe
    assert rec["run_source"] == "dispatch"


def test_validate_costmodel_block_rejects_malformed():
    assert telemetry.validate_costmodel_block([]) != []
    assert telemetry.validate_costmodel_block({"kernels": 3}) != []
    bad_bound = {"kernels": {"k": {"flops": 1.0, "bytes_accessed": 1.0,
                                   "bound": "weird"}},
                 "watermarks": {}}
    assert any("bound" in p
               for p in telemetry.validate_costmodel_block(bad_bound))
    bad_wm = {"kernels": {},
              "watermarks": {"cpu:0": {"high_water_bytes": 1,
                                       "last_bytes": 2}}}
    assert any("high water" in p
               for p in telemetry.validate_costmodel_block(bad_wm))


# --- disabled-path contract --------------------------------------------------


def test_costmodel_requires_both_gates():
    costmodel.configure(enabled=None)     # back to the env gate (off)
    assert not costmodel.enabled()
    costmodel.configure(enabled=True)
    telemetry.configure(enabled=False)
    assert not costmodel.enabled()        # telemetry gate still applies
    telemetry.configure(enabled=True)
    assert costmodel.enabled()


def test_disabled_capture_and_watermark_are_noops():
    costmodel.configure(enabled=False)
    assert costmodel.capture("k@1", object(), (1,)) is None
    assert costmodel.sample_watermark("t") == {}
    assert costmodel.raw_snapshot() == {
        "kernels": {}, "watermarks": {}, "wm_events": 0,
        "wm_events_dropped": 0}
    assert costmodel.block() is None


def test_disabled_noop_bound():
    """The off path must stay off the profile: a capture +
    sample_watermark pair under 6 microseconds amortized (flag checks,
    no lowering, no device walk) — same budget style as the telemetry
    no-op test."""
    costmodel.configure(enabled=False)
    n = 20_000
    t0 = time.perf_counter()
    for _ in range(n):
        costmodel.capture("k", None, ())
        costmodel.sample_watermark("t")
    per_pair = (time.perf_counter() - t0) / n
    assert per_pair < 6e-6, f"no-op pair cost {per_pair * 1e6:.2f}us"
