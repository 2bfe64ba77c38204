"""Small JAX process-setup helpers shared by the entry points, and the
build ledger: where the seconds of building each jitted function go."""

from __future__ import annotations

import heapq
import os
import sys
import threading
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent.parent
DEFAULT_CACHE_DIR = REPO_ROOT / ".jax_cache"

# the three phases JAX times when it builds a function, by event name;
# "compile" includes a load from the persistent compilation cache
_BUILD_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_builds_lock = threading.Lock()
_builds: list = []          # (function name, phase, start, end), wall clock
_watching = False


def _note_build(event: str, start: float, end: float, fun_name: str = "",
                **_) -> None:
    phase = _BUILD_PHASES.get(event)
    if phase is None:
        return
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        fun_name = fun_name[4:-1]
    with _builds_lock:
        _builds.append((fun_name, phase, start, end))


def watch_builds() -> None:
    """Start the build ledger (once per process): from here on every
    trace, lowering and compile JAX records is kept."""
    global _watching
    with _builds_lock:
        if _watching:
            return
        _watching = True
    import jax.monitoring

    jax.monitoring.register_event_time_span_listener(_note_build)


def jit(fun, **kwargs):
    """`jax.jit(fun)` with the build ledger watching; `fun.__name__`
    names the program in the ledger and in profiler traces."""
    import jax

    watch_builds()
    return jax.jit(fun, **kwargs)


def builds() -> dict:
    """{function name: {"count", "trace_s", "lower_s", "compile_s"}}
    since `watch_builds()`; `count` counts compiles (cache loads
    included).  A function traced inside another one's trace is timed
    in both rows."""
    out: dict = {}
    with _builds_lock:
        rows = list(_builds)
    for name, phase, start, end in rows:
        row = out.setdefault(name, {"count": 0, "trace_s": 0.0,
                                    "lower_s": 0.0, "compile_s": 0.0})
        row[f"{phase}_s"] += end - start
        row["count"] += phase == "compile"
    return out


def build_seconds() -> dict:
    """{"trace_s", "lower_s", "compile_s"}: wall seconds in each phase
    since `watch_builds()`.  Each instant counts once, for the innermost
    build open then: a function traced, or a constant compiled, inside
    another one's trace is not counted twice, so the three add up to the
    time spent building."""
    with _builds_lock:
        rows = sorted((s, e, p) for _, p, s, e in _builds)
    out = {"trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.0}
    edges = sorted({t for s, e, _ in rows for t in (s, e)})
    started: list = []        # heap of (-start, end, phase): latest on top
    i = 0
    for lo, hi in zip(edges, edges[1:]):
        while i < len(rows) and rows[i][0] <= lo:
            heapq.heappush(started, (-rows[i][0], rows[i][1], rows[i][2]))
            i += 1
        while started and started[0][1] <= lo:
            heapq.heappop(started)
        if started:
            out[f"{started[0][2]}_s"] += hi - lo
    return out


def enable_compile_cache() -> None:
    """Turn on JAX's persistent compilation cache for every compile.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and the
    directory is left alone; otherwise the cache lives at the fixed,
    gitignored `.jax_cache/` of the checkout (the path is part of the
    cache key, so it must not move between runs).  A failure to set the
    cache up is reported on stderr and the process goes on uncached.

    Telemetry records the chosen directory and its entry count at setup;
    cache HITS are not observable through jax's config API, so they are
    inferred downstream from first-call latency (a hit makes the
    `kernel.compile_first_s` sample collapse toward `kernel.run_s` —
    see the README's telemetry notes)."""
    import jax

    from .. import telemetry

    try:
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            DEFAULT_CACHE_DIR.mkdir(exist_ok=True)
            jax.config.update("jax_compilation_cache_dir",
                              str(DEFAULT_CACHE_DIR))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    except Exception as exc:   # cst: allow(exc-swallow-device): the cache is an optimisation; the failure is reported, not hidden
        print(f"compile cache not enabled: {type(exc).__name__}: {exc}",
              file=sys.stderr, flush=True)
        return
    if telemetry.enabled():
        d = Path(jax.config.jax_compilation_cache_dir)
        telemetry.set_meta("compile_cache.dir", str(d))
        telemetry.set_meta("compile_cache.entries_at_start",
                           sum(1 for p in d.iterdir() if p.is_file())
                           if d.is_dir() else 0)
