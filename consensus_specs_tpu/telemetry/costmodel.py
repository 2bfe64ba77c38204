"""Kernel cost model: XLA cost/memory analysis + roofline + watermarks.

Telemetry (core.py) answers *how long* each kernel takes; this layer
answers *why*.  Per compiled kernel it captures XLA's own static
analyses — `lowered.compile().cost_analysis()` (flops, bytes accessed,
transcendentals) and `memory_analysis()` (argument / output / temp /
generated-code bytes) — joins them with the measured `run_s` from the
compile-vs-run split, and derives the roofline numbers the ROADMAP's
open perf questions need: achieved FLOP/s, achieved bytes/s, arithmetic
intensity, and a compute- / memory- / launch-bound classification
against a small peak registry keyed by `device_kind` (TPU peaks read
from `BASELINE.json`'s `"peaks"` section; CPU peaks are built-in and
marked advisory).  It also samples per-device live-buffer bytes at span
boundaries (device-memory watermarks, high-water mark kept per device).

Gating contract, strictly additive to core.py's: everything here is OFF
unless BOTH the telemetry registry is collecting AND `CST_COSTMODEL` is
set to a non-empty value other than "0" (cost capture without the run_s
histograms to join against would be numbers with no denominator).  The
disabled paths are a single flag check — `capture()` and
`sample_watermark()` return before touching their arguments, so the hot
path instruments unconditionally, exactly like `telemetry.span`.

Capture cost: `capture()` runs once per kernel key per process.  The
AOT `lower().compile()` pass usually lands in the jit/XLA compile cache
the kernel's real dispatch already populated; the one timed re-run that
gives every cost record a steady-state wall sample is a real extra
kernel execution — acceptable for an explicitly-enabled cost round,
never paid otherwise.

Zero dependencies: stdlib only at import time.  jax is never imported
here — `capture()` only uses the jit object it is handed, and
`sample_watermark()` reads jax out of `sys.modules` (a telemetry layer
must not initialize a backend; same rule as core.py).
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from pathlib import Path

from . import core

# watermark trace-event buffer cap (counter events are ~80 bytes each);
# drops are counted, never silent — mirrors core._MAX_EVENTS
_MAX_WM_EVENTS = 50_000

# a kernel whose roofline-predicted time (max of compute / memory legs)
# covers less than this fraction of its measured wall is dominated by
# dispatch overhead, not by the work XLA counted: launch-bound
LAUNCH_BOUND_FRAC = 0.05

# built-in peaks keyed by jax `device_kind` ("cpu" is the CPU backend's
# kind); `BASELINE.json`'s "peaks" section overrides per key.  A device
# kind with no row gets no peak: its kernels stay unclassified rather
# than being measured against another chip's roofline.  CPU entries are
# advisory: a portable CI host has no single honest peak, so its
# utilization numbers rank kernels against each other rather than
# against the hardware.
_DEFAULT_PEAKS = {
    "TPU v5 lite": {"flops_per_s": 1.97e14, "bytes_per_s": 8.19e11,
                    "advisory": False,
                    "note": "TPU v5e published bf16 peak / HBM bandwidth",
                    "source": "Google Cloud documentation, 'TPU v5e'"},
    "cpu": {"flops_per_s": 5.0e10, "bytes_per_s": 2.0e10,
            "advisory": True,
            "note": "generic CI-host estimate — advisory only"},
}

_lock = threading.Lock()

_costs: dict[str, dict] = {}          # kernel key -> raw capture record
_watermarks: dict[str, dict] = {}     # device -> last/high-water/samples
_wm_events: list[dict] = []           # chrome-trace counter samples
_wm_events_dropped = 0
_peaks_cache: dict | None = None


def _env_enabled() -> bool:
    return os.environ.get("CST_COSTMODEL", "0") not in ("", "0")


_env_on = _env_enabled()
_override: bool | None = None


def enabled() -> bool:
    """True when cost capture is armed: the telemetry registry is
    collecting AND CST_COSTMODEL is set (or `configure(enabled=True)`
    overrode the env gate)."""
    if not core.enabled():
        return False
    return _env_on if _override is None else _override


def configure(enabled: bool | None = None) -> None:
    """Programmatic override of the CST_COSTMODEL env gate (tests and
    benches); the telemetry-registry gate still applies on top."""
    global _override
    _override = enabled


def _reset_state() -> None:
    """Full wipe — called by `core.reset(full=True)` so test isolation
    clears cost records and watermarks along with the first-call keys
    they attribute against.  Per-config `core.reset()` does NOT clear
    this registry: a kernel's cost is a per-process fact (like the
    compile attribution keys), owed to every config's export."""
    global _wm_events_dropped, _peaks_cache
    with _lock:
        _costs.clear()
        _watermarks.clear()
        _wm_events.clear()
        _wm_events_dropped = 0
        _peaks_cache = None


# --- peak registry -----------------------------------------------------------


def _baseline_path() -> Path:
    return Path(__file__).resolve().parents[2] / "BASELINE.json"


def peaks() -> dict:
    """The peak registry by device kind: built-in defaults overlaid with
    `BASELINE.json`'s `"peaks"` section (per device kind, per key).  A
    missing or malformed file degrades to the defaults — the cost model
    must never crash the path it observes."""
    global _peaks_cache
    with _lock:
        if _peaks_cache is not None:
            return _peaks_cache
    merged = {k: dict(v) for k, v in _DEFAULT_PEAKS.items()}
    try:
        data = json.loads(_baseline_path().read_text())
        overlay = data.get("peaks")
        if isinstance(overlay, dict):
            for backend, entry in overlay.items():
                if not isinstance(entry, dict):
                    continue
                merged.setdefault(str(backend), {}).update(entry)
    except (OSError, json.JSONDecodeError, UnicodeDecodeError):
        pass
    with _lock:
        _peaks_cache = merged
    return merged


_warned_kinds: set[str] = set()


def peaks_for(device_kind: str | None) -> dict | None:
    """Peak entry for a jax `device_kind` ('TPU v5 lite', 'cpu', ...),
    matched exactly (case-insensitive).  An unknown kind gets None and
    one warning on stderr: no other chip's peaks stand in for it."""
    if not device_kind:
        return None
    reg = peaks()
    kind = str(device_kind)
    for key, entry in reg.items():
        if key.lower() == kind.lower():
            return dict(entry, backend=key)
    if kind not in _warned_kinds:
        _warned_kinds.add(kind)
        print(f"costmodel: no peaks for device kind {kind!r}; its "
              f"kernels stay unclassified", file=sys.stderr, flush=True)
    return None


# --- classification ----------------------------------------------------------


def classify(flops: float, bytes_accessed: float, run_s: float | None,
             peak: dict | None) -> dict:
    """Roofline-derive one kernel's utilization numbers.

    Returns {arithmetic_intensity, achieved_flops_per_s,
    achieved_bytes_per_s, util_flops_pct, util_bw_pct, bound}; `bound`
    is "compute" | "memory" | "launch" | "unknown".  The classification
    compares the two roofline legs (flops/peak_flops vs
    bytes/peak_bandwidth): whichever leg is longer binds — unless both
    together explain under LAUNCH_BOUND_FRAC of the measured wall, in
    which case dispatch overhead dominates and the kernel is
    launch-bound (the `_MSM_DEVICE_MIN` small-n regime)."""
    out: dict = {
        "arithmetic_intensity":
            round(flops / bytes_accessed, 4) if bytes_accessed else None,
        "achieved_flops_per_s": None,
        "achieved_bytes_per_s": None,
        "util_flops_pct": None,
        "util_bw_pct": None,
        "bound": "unknown",
    }
    if run_s and run_s > 0:
        out["achieved_flops_per_s"] = round(flops / run_s, 1)
        out["achieved_bytes_per_s"] = round(bytes_accessed / run_s, 1)
    if peak is None or not run_s or run_s <= 0:
        return out
    t_compute = flops / peak["flops_per_s"] if peak.get("flops_per_s") \
        else 0.0
    t_memory = bytes_accessed / peak["bytes_per_s"] \
        if peak.get("bytes_per_s") else 0.0
    out["util_flops_pct"] = round(t_compute / run_s * 100.0, 2)
    out["util_bw_pct"] = round(t_memory / run_s * 100.0, 2)
    if max(t_compute, t_memory) < LAUNCH_BOUND_FRAC * run_s:
        out["bound"] = "launch"
    elif t_compute >= t_memory:
        out["bound"] = "compute"
    else:
        out["bound"] = "memory"
    return out


# --- capture -----------------------------------------------------------------


def _normalize_cost(ca) -> dict:
    """`compiled.cost_analysis()` is a dict on new jax, a list of dicts
    (one per computation) on 0.4.x — normalize to one flat dict."""
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return ca if isinstance(ca, dict) else {}


def _memory_dict(compiled) -> dict | None:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    out = {}
    for key in ("argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "alias_size_in_bytes",
                "generated_code_size_in_bytes"):
        v = getattr(ma, key, None)
        if isinstance(v, int):
            out[key] = v
    return out or None


def capture(kernel: str, fn, args, kwargs=None) -> dict | None:
    """AOT cost/memory analysis for one jitted kernel, once per kernel
    key per process.  `fn` is the jit-wrapped callable the seam just
    dispatched (its jit cache is warm, so the timed re-run below is a
    steady-state sample); `args` are the exact call arguments.

    Never raises: a backend that cannot lower/analyze (mesh-sharded
    executables, exotic platforms) stores an error record and bumps the
    `costmodel.capture_errors` counter instead — the kernel stays
    visible, with the reason attached.  Disabled mode is a flag check
    returning None."""
    if not enabled():
        return None
    with _lock:
        if kernel in _costs:
            return _costs[kernel]
    t_cap = time.perf_counter()
    rec: dict = {"kernel": kernel,
                 "ts_rel_us": round((t_cap - core._T0) * 1e6, 1)}
    try:
        jax = sys.modules.get("jax")
        lowered = fn.lower(*args, **(kwargs or {}))
        compiled = lowered.compile()
        ca = _normalize_cost(compiled.cost_analysis())
        rec["flops"] = float(ca.get("flops", 0.0))
        rec["bytes_accessed"] = float(ca.get("bytes accessed", 0.0))
        rec["transcendentals"] = float(ca.get("transcendentals", 0.0))
        mem = _memory_dict(compiled)
        if mem:
            rec["memory"] = mem
        if jax is not None:
            rec["platform"] = jax.devices()[0].platform
            rec["device_kind"] = jax.devices()[0].device_kind
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args, **(kwargs or {})))
            rec["run_s_probe"] = round(time.perf_counter() - t0, 6)
    except Exception as e:
        rec["error"] = f"{type(e).__name__}: {e}"[:300]
        core.count("costmodel.capture_errors")
    with _lock:
        _costs.setdefault(kernel, rec)
    core.count("costmodel.captured")
    return rec


def record_cost(kernel: str, flops: float, bytes_accessed: float,
                transcendentals: float = 0.0, platform: str = "cpu",
                device_kind: str | None = None,
                run_s_probe: float | None = None,
                memory: dict | None = None) -> None:
    """Direct cost-record injection (tests and synthetic report rounds);
    same gating and once-per-key semantics as `capture`."""
    if not enabled():
        return
    rec = {"kernel": kernel, "flops": float(flops),
           "bytes_accessed": float(bytes_accessed),
           "transcendentals": float(transcendentals),
           "platform": platform,
           "device_kind": device_kind or platform,
           "ts_rel_us": round((time.perf_counter() - core._T0) * 1e6, 1)}
    if run_s_probe is not None:
        rec["run_s_probe"] = float(run_s_probe)
    if memory:
        rec["memory"] = dict(memory)
    with _lock:
        _costs.setdefault(kernel, rec)


# --- device-memory watermarks ------------------------------------------------


def _device_live_bytes(jax) -> dict[str, int]:
    """Per-device live-buffer bytes.  `memory_stats()` (TPU: allocator
    truth incl. fragmentation) wins; backends without it (XLA:CPU) fall
    back to summing `jax.live_arrays()` per committed device — a sharded
    array counts fully on each of its devices."""
    out: dict[str, int] = {}
    try:
        devices = jax.devices()
    except Exception:
        return out
    stats_seen = False
    for d in devices:
        label = f"{d.platform}:{d.id}"
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if isinstance(stats, dict) and "bytes_in_use" in stats:
            out[label] = int(stats["bytes_in_use"])
            stats_seen = True
    if stats_seen:
        return out
    # live-array fallback: seed every device at 0 so a sample taken
    # while nothing is resident still records (an idle device IS at
    # zero live bytes — dropping the sample would hide exactly the
    # moments the watermark timeline needs between kernel bursts)
    for d in devices:
        out[f"{d.platform}:{d.id}"] = 0
    try:
        for a in jax.live_arrays():
            for d in a.devices():
                label = f"{d.platform}:{d.id}"
                out[label] = out.get(label, 0) + int(a.nbytes)
    except Exception:
        pass
    return out


def sample_watermark(tag: str = "") -> dict[str, int]:
    """Sample per-device live-buffer bytes NOW, update the per-device
    high-water mark, and buffer a Chrome-trace counter sample.  Called
    at span boundaries (executor phases, kernel dispatch); a no-op flag
    check while disabled or before jax ever imported."""
    if not enabled():
        return {}
    jax = sys.modules.get("jax")
    if jax is None:
        return {}
    sample = _device_live_bytes(jax)
    if not sample:
        return {}
    ts_rel_us = round((time.perf_counter() - core._T0) * 1e6, 1)
    global _wm_events_dropped
    with _lock:
        for dev, nbytes in sample.items():
            wm = _watermarks.get(dev)
            if wm is None:
                _watermarks[dev] = {"last_bytes": nbytes,
                                    "high_water_bytes": nbytes,
                                    "samples": 1}
            else:
                wm["last_bytes"] = nbytes
                if nbytes > wm["high_water_bytes"]:
                    wm["high_water_bytes"] = nbytes
                wm["samples"] += 1
        if len(_wm_events) < _MAX_WM_EVENTS:
            _wm_events.append({"ts": ts_rel_us, "tag": tag,
                               "bytes": dict(sample)})
        else:
            _wm_events_dropped += 1
    return sample


# --- snapshot / join ---------------------------------------------------------


def watermark_bytes() -> dict[str, int]:
    """`{device: last_bytes}` — the cheap point read behind the SLO
    watchdog's memory-slope signal and the exposition endpoint's
    per-device gauges (no kernel-record copy, unlike `raw_snapshot`)."""
    with _lock:
        return {dev: wm["last_bytes"] for dev, wm in _watermarks.items()}


def raw_snapshot() -> dict:
    """The captured state as-is (no derived metrics): what
    `telemetry.snapshot()["costmodel"]` carries.  Schema:

        {"kernels":    {key: raw capture record},
         "watermarks": {device: {"last_bytes", "high_water_bytes",
                                 "samples"}},
         "wm_events": int, "wm_events_dropped": int}
    """
    with _lock:
        return {
            "kernels": {k: dict(v) for k, v in _costs.items()},
            "watermarks": {k: dict(v) for k, v in _watermarks.items()},
            "wm_events": len(_wm_events),
            "wm_events_dropped": _wm_events_dropped,
        }


def _wm_events_copy() -> tuple[list[dict], int]:
    with _lock:
        return ([dict(e) for e in _wm_events], _wm_events_dropped)


def _cost_events_copy() -> list[dict]:
    with _lock:
        return [dict(v) for v in _costs.values()]


def join_record(raw: dict, hists: dict) -> dict:
    """One kernel's raw capture record joined with the measured run_s
    from the telemetry compile-vs-run split and classified against the
    peak registry.  `hists` is `snapshot()["histograms"]`; the
    per-kernel `kernel.<key>.run_s` mean (real steady-state iterations)
    outranks the capture-time probe sample."""
    rec = dict(raw)
    if "error" in rec:
        return rec
    key = rec.get("kernel", "")
    run_hist = hists.get(f"kernel.{key}.run_s")
    if isinstance(run_hist, dict) and run_hist.get("count"):
        rec["run_s_mean"] = round(
            run_hist["total"] / run_hist["count"], 6)
        rec["run_source"] = "dispatch"
    elif rec.get("run_s_probe") is not None:
        rec["run_s_mean"] = rec["run_s_probe"]
        rec["run_source"] = "probe"
    else:
        rec["run_s_mean"] = None
        rec["run_source"] = "none"
    comp_hist = hists.get(f"kernel.{key}.compile_first_s")
    if isinstance(comp_hist, dict) and comp_hist.get("count"):
        rec["compile_first_s"] = round(comp_hist["total"], 4)
    peak = peaks_for(rec.get("device_kind"))
    rec.update(classify(rec.get("flops", 0.0),
                        rec.get("bytes_accessed", 0.0),
                        rec["run_s_mean"], peak))
    if peak is not None:
        rec["peak_source"] = peak["backend"] + (
            " (advisory)" if peak.get("advisory") else "")
    return rec


def block(hists: dict | None = None) -> dict | None:
    """The `"costmodel"` sub-object for the bench `"telemetry"` block:
    every captured kernel joined + classified, plus the watermark
    summary and the peak registry actually used.  None while disabled
    (the bench contract omits the key)."""
    if not enabled():
        return None
    if hists is None:
        hists = core.snapshot()["histograms"]
    raw = raw_snapshot()
    return {
        "kernels": {k: join_record(v, hists)
                    for k, v in raw["kernels"].items()},
        "watermarks": raw["watermarks"],
        "peaks": peaks(),
    }
