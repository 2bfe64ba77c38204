"""Small JAX process-setup helpers shared by the entry points."""

from __future__ import annotations

import os
import sys
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent.parent
DEFAULT_CACHE_DIR = REPO_ROOT / ".jax_cache"


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


def device_fields() -> dict:
    """What this process's devices report, stamped on every bench
    result so that no number is read as another device's."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs)}
