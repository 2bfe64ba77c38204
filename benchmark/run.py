"""Run one benchmark cell and print its result as the last line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Everything a cell needs is found by name from `BENCHMARK.json`: its
configuration file, its traffic file `benchmark/traffic/<traffic>.json`,
the system that drives it `benchmark/systems/<config's "system">.py`, and
one reader per per-layer metric, `benchmark/metrics/<metric>.py`.

A run makes its inputs from the seed, warms the one program shape the
cell uses (set-up, timed from process start), measures for `--seconds`,
frees the device, then checks what the window produced against the plain
reference.  With `--trace 1` the window runs under the JAX profiler and
the result carries the per-layer metrics; with `--trace 0`, the cell's
end-to-end metrics.  The numbers compared, each with its limit, are the
last lines on standard error and the last key of the result.  Without a
TPU, or with fewer chips than the cell asks for, it exits 2 and prints no
result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmark import harness, trace  # noqa: E402


def load_cell(name: str) -> dict:
    """The cell's entry, configuration, traffic and metric entries."""
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    config_entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]

    def reports(metric):
        return name in metric.get("workloads", [name])

    return {
        "cell": cell,
        "config": json.loads((harness.ROOT / config_entry["file"])
                             .read_text()),
        "traffic": json.loads((harness.BENCH_DIR / "traffic"
                               / f"{cell['traffic']}.json").read_text()),
        "end_to_end": [m for m in spec["end_to_end"] if reports(m)],
        "per_layer": [m for m in spec["per_layer"] if reports(m)],
    }


def system_class(loaded: dict):
    return importlib.import_module(
        f"benchmark.systems.{loaded['config']['system']}").System


def read_per_layer(metrics: list, ctx: dict) -> dict:
    """Each reader's value; a reader that finds nothing is left out."""
    out = {}
    for m in metrics:
        reader = harness.load_module(harness.BENCH_DIR / "metrics"
                                     / f"{m['name']}.py")
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run(loaded: dict, seed: int, seconds: float, traced: bool,
        devices=None, **options) -> dict:
    """One run of a cell; returns the result object.  `devices` is given by
    the CPU rehearsal, which skips the look for a chip."""
    span = harness.span if traced else harness.no_span
    cls = system_class(loaded)
    if devices is None:
        jax, devices = harness.start_jax(loaded["cell"]["chips"])
    else:
        import jax
    system = cls(loaded["config"], loaded["traffic"], seed, seconds, span,
                 **options)
    system.prepare()
    system.setup(jax, devices)
    setup_s = time.perf_counter() - T_START

    tracer = None
    if traced:
        op_scopes = trace.hlo_op_scopes(system.program_text()) if hasattr(
            system, "program_text") else {}
        tracer = trace.Tracer(getattr(system, "TRACE_SECONDS", None),
                              getattr(system, "TPU_TRACE_MODE", None))
    compiles = harness.CompileCounter()
    try:
        end_to_end = system.window(tracer or trace.NO_TRACER)
    finally:
        if tracer:
            tracer.stop()
    if compiles.count:
        harness.log(f"{compiles.count} XLA compiles inside the measured "
                    f"window")
    device = harness.device_block(devices)
    system.release()

    breakdown = None
    if traced:
        tr = trace.read(tracer.path(), op_scopes)
        tracer.remove()
        ctx = {"trace": tr, "counters": system.counters(),
               "peaks": harness.peaks(device["kind"])
               if device["platform"] == "tpu" else None}
        metrics = read_per_layer(loaded["per_layer"], ctx)
        device["busy_s"] = trace.busy_ns(tr) / 1e9
        device["window_s"] = trace.window_ns(tr) / 1e9
        breakdown = trace.breakdown(tr)
    else:
        values = dict(end_to_end, setup_s=setup_s)
        missing = [m["name"] for m in loaded["end_to_end"]
                   if m["name"] not in values]
        if missing:
            raise RuntimeError(f"the system reported no {missing}")
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in loaded["end_to_end"]}

    verdict = system.check()
    checks = {k: {"value": v, "limit": lim}
              for k, (v, lim) in verdict["checks"].items()}
    result = {"correct": all(c["value"] <= c["limit"]
                             for c in checks.values()),
              "attempted": verdict["attempted"], "failed": verdict["failed"],
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for key, value in verdict.get("notes", {}).items():
        harness.log(f"note {key}: {value}")
    for key, c in checks.items():
        harness.log(f"check {key}: {c['value']} (limit {c['limit']})")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    loaded = load_cell(args.workload)
    try:
        result = run(loaded, args.seed, args.seconds, bool(args.trace))
    except harness.NoChip as exc:
        harness.log(f"benchmark: {exc}")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
