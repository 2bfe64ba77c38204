"""The readers of the program's own spans and build ledger, on a trace
recorded on one TPU v5 lite with the program's `cst.*` host spans
(`fixtures/record_program_spans.py`) and on synthetic counts."""

import importlib.util
import json
from pathlib import Path

import pytest

from benchmark import trace
from benchmark.harness import BENCH_DIR
from consensus_specs_tpu import telemetry
from consensus_specs_tpu.utils import jaxtools

FIXTURES = Path(__file__).resolve().parent / "fixtures"
PHASES = ("cst.bls.decompress_g1", "cst.bls.subgroup_g1",
          "cst.bls.decompress_g2", "cst.bls.subgroup_g2")


def reader(name):
    path = BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"),
                                                  path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


@pytest.fixture(scope="module")
def recorded():
    """The trace, its reduction, its `cst.*` host events as (name, line,
    start, end), and the program's own count taken with it."""
    from jax.profiler import ProfileData

    path = str(FIXTURES / "program_spans.xplane.pb")
    events = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("cst.", "bench.submit")):
                    events.append((ev.name, line.name, ev.start_ns,
                                   ev.start_ns + ev.duration_ns))
    counted = json.loads((FIXTURES / "program_spans.json").read_text())
    return trace.read(path), events, counted


def test_the_reduction_keeps_harness_spans_only(recorded):
    tr, events, _ = recorded
    assert {s.name for s in tr.spans} == {"bench.submit", "bench.step"}
    assert tr.devices == ["/device:TPU:0"]
    assert any(name.startswith("cst.") for name, *_ in events)


def test_program_spans_nest_in_the_harness_spans(recorded):
    _, events, _ = recorded
    submits = [e for e in events if e[0] == "bench.submit"]
    parses = [e for e in events if e[0] == "cst.serve.parse"]
    assert len(submits) == len(parses) == 3

    def inside(e, outer):
        return any(o[1] == e[1] and o[2] <= e[2] <= e[3] <= o[3]
                   for o in outer)

    assert all(inside(p, submits) for p in parses)
    phases = [e for e in events if e[0] in PHASES]
    assert sorted({e[0] for e in phases}) == sorted(PHASES)
    assert len(phases) == 4 * 3
    assert all(inside(e, parses) for e in phases)


def test_program_count_agrees_with_the_trace(recorded):
    _, events, counted = recorded
    for name, c in counted.items():
        durs = [(e - s) / 1e9 for n, _, s, e in events
                if n == "cst." + name]
        assert len(durs) == c["count"], name
        # the program reads its clock beside the annotation's own
        assert c["total_s"] == pytest.approx(sum(durs), abs=5e-5 * c["count"])


def test_readers_on_the_recorded_window(recorded, monkeypatch):
    tr, _, counted = recorded
    monkeypatch.setattr(telemetry, "profiled_spans", lambda: counted)
    ctx = {"trace": tr, "counters": {}}
    parse = reader("parse_ms")(ctx)
    assert parse == pytest.approx(counted["serve.parse"]["total_s"] / 3 * 1e3)
    subgroup = reader("subgroup_check_ms")(ctx)
    assert 0 < subgroup < parse <= reader("submit_ms")(ctx)
    # the fixture dispatches no batch
    assert reader("batch_prep_ms")(ctx) is None


def test_subgroup_check_and_batch_prep_arithmetic(monkeypatch):
    counts = {"serve.parse": {"count": 4, "total_s": 0.08},
              "bls.subgroup_g1": {"count": 4, "total_s": 0.016},
              "bls.subgroup_g2": {"count": 4, "total_s": 0.048},
              "bls.prepare": {"count": 2, "total_s": 0.1},
              "bls.enqueue": {"count": 2, "total_s": 0.02}}
    monkeypatch.setattr(telemetry, "profiled_spans", lambda: counts)
    assert reader("parse_ms")({}) == pytest.approx(20.0)
    assert reader("subgroup_check_ms")({}) == pytest.approx(16.0)
    assert reader("batch_prep_ms")({}) == pytest.approx(60.0)


@pytest.mark.parametrize("name, want", [("build_trace_lower_s", 5.0),
                                        ("build_compile_s", 7.0)])
def test_build_readers(monkeypatch, name, want):
    monkeypatch.setattr(jaxtools, "builds", lambda: {"step": {}})
    monkeypatch.setattr(jaxtools, "build_seconds", lambda: {
        "trace_s": 3.0, "lower_s": 2.0, "compile_s": 7.0})
    dev = "/device:TPU:0"
    busy = trace.Trace([], [trace.Op(dev, "run 1", "", 0.0, 1.0)], [],
                       (0.0, 2.0), [dev])
    assert reader(name)({"trace": busy}) == want
    # a window with no device work, as a CPU rehearsal's
    assert reader(name)({"trace": busy._replace(devices=[])}) is None


def test_new_readers_find_nothing_in_a_program_without_them(monkeypatch):
    """The parent program has neither the span count nor the ledger."""
    monkeypatch.delattr(telemetry, "profiled_spans")
    monkeypatch.delattr(jaxtools, "build_seconds")
    dev = "/device:TPU:0"
    ctx = {"trace": trace.Trace([], [], [], (0.0, 1.0), [dev])}
    for name in ("parse_ms", "subgroup_check_ms", "batch_prep_ms",
                 "build_trace_lower_s", "build_compile_s"):
        assert reader(name)(ctx) is None, name
