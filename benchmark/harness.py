"""What every cell shares: the chip check, the compile cache, the spans,
the compile counter, the device block and the metric readers' lookup."""

from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_module(path: Path):
    """Import a file by path (names of readers may hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def start_jax(chips: int):
    """JAX with uint64 on and the persistent compile cache at
    `JAX_COMPILATION_CACHE_DIR`, or else at the fixed `.jax_cache/` of the
    checkout; raises NoChip unless it sees `chips` TPU chips or more."""
    import jax

    jax.config.update("jax_enable_x64", True)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        cache = ROOT / ".jax_cache"
        cache.mkdir(exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    try:
        devices = jax.devices()
    except RuntimeError as exc:
        raise NoChip(f"JAX found no device: {exc}") from exc
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found platform "
                     f"{devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devices)}")
    return jax, devices[:chips]


def peaks(device_kind: str) -> dict:
    """The peak row of a device kind; an unknown kind is an error."""
    table = json.loads((BENCH_DIR / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"benchmark/peaks.json")
    return table["devices"][device_kind]


def device_block(devices) -> dict:
    """Platform, kind and count as JAX reports them, and the peak bytes in
    use on the fullest chip."""
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(peak)}


class CompileCounter:
    """Counts XLA backend compiles (cache hits included) while installed."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.count = 0

        def listener(event, duration, **kwargs):
            if event == self.EVENT:
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(listener)


def span(name: str):
    """A host span on the profiler's clock (free when no trace runs)."""
    import jax.profiler

    return jax.profiler.TraceAnnotation(name)


def no_span(name: str):
    return contextlib.nullcontext()


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)
