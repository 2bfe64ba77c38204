"""Serving subsystem — deferred-result futures + the batching executor.

The spine of a serving system (ROADMAP: "Async deferred-result device
API" + "sustained-load attestation-verification service"): every device
result in this repo — pairing bools, MSM points, sha256 roots, fr_batch
field elements — is available as a `DeviceFuture` handle
(`serve.futures`), and `ServeExecutor` (`serve.executor`) drains a
request queue into AOT-warmed executables on the `_bucket` shape
ladder, settling futures in topological batches while the host keeps
preparing the next batch (double-buffered: batch N settles only after
batch N+1 has been dispatched).

`serve.loadgen` drives the executor at (multiples of) mainnet per-slot
rates and reports steady-state verifies/sec plus p50/p99 batch latency;
`python -m consensus_specs_tpu.serve` is the CLI.

Import discipline: this package init imports ONLY `futures` eagerly —
the ops device modules import `serve.futures` for their async APIs, and
`serve.executor` imports the ops modules, so the executor/loadgen names
resolve lazily to keep the import graph acyclic.
"""

from __future__ import annotations

from . import futures
from .futures import DeviceFuture, FutureError, bool_future, value_future

__all__ = [
    "DeviceFuture", "FutureError", "ServeExecutor", "bool_future",
    "futures", "run_load", "value_future",
]

_LAZY = {
    "ServeExecutor": ("executor", "ServeExecutor"),
    "executor": ("executor", None),
    "loadgen": ("loadgen", None),
    "run_load": ("loadgen", "run_load"),
}


def __getattr__(name):
    entry = _LAZY.get(name)
    if entry is None:
        raise AttributeError(name)
    import importlib

    module = importlib.import_module(f".{entry[0]}", __name__)
    return module if entry[1] is None else getattr(module, entry[1])
