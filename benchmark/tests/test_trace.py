"""The trace reduction on traces recorded on one TPU v5 lite, and one test
per per-layer metric reader."""

import importlib.util
from pathlib import Path

import pytest

from benchmark import trace
from benchmark.harness import BENCH_DIR

FIXTURES = Path(__file__).resolve().parent / "fixtures"
SCOPES = {"sweep": ("cst.epoch_sweep",),
          "merkle": ("cst.balances_list_root",)}


def reader(name):
    path = BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"),
                                                  path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


@pytest.fixture(scope="module")
def tpu_trace():
    """Two steps of a small program with a scope per epoch layer
    (`fixtures/record_tpu_trace.py`)."""
    scopes = trace.hlo_op_scopes(
        (FIXTURES / "tpu_trace.hlo.txt").read_text())
    return trace.read(str(FIXTURES / "tpu_trace.xplane.pb"), scopes)


def synthetic():
    """Two device ops and one program run in a 10 ms window, with a
    submit span over the idle stretch."""
    dev = "/device:TPU:0"
    ops = [trace.Op(dev, "fusion.1", "jit(_step)/cst.epoch_sweep/add",
                    1e6, 3e6),
           trace.Op(dev, "fusion.2", "jit(_step)/cst.balances_list_root",
                    4e6, 1e6)]
    modules = [trace.Op(dev, "jit_run(123)", "", 1e6, 4e6)]
    spans = [trace.Span("bench.step", 0.0, 5e6),
             trace.Span("bench.step", 5e6, 5e6),
             trace.Span("bench.submit", 5e6, 5e6),
             trace.Span("bench.submit", 6e6, 1e6)]
    return trace.Trace(ops=ops, modules=modules, spans=spans,
                       window=(0.0, 10e6), devices=[dev])


def test_reads_ops_spans_and_window(tpu_trace):
    assert tpu_trace.devices == ["/device:TPU:0"]
    assert {s.name for s in tpu_trace.spans} == {"bench.step",
                                                 "bench.submit"}
    assert len(tpu_trace.modules) == 2
    lo, hi = tpu_trace.window
    assert all(lo <= o.start_ns and o.start_ns + o.dur_ns <= hi
               for o in tpu_trace.ops + tpu_trace.modules)
    scoped = {p for o in tpu_trace.ops for p in o.scope.split("/")
              if p.startswith("cst.")}
    assert scoped == {"cst.epoch_sweep", "cst.balances_list_root"}


def test_window_moves_to_the_device_clock(tpu_trace):
    """The fixture's device plane reads its first program run before the
    host opened the window span: the window starts there, and keeps the
    span's length."""
    assert tpu_trace.window[0] == min(m.start_ns for m in tpu_trace.modules)
    assert trace.window_ns(tpu_trace) == 22697558.0
    assert len(trace.whole_runs(tpu_trace)) == 2


def test_a_run_cut_by_the_window_edge():
    dev = "/device:TPU:0"
    tr = trace.Trace(ops=[], modules=[trace.Op(dev, "run 1", "", 0.0, 4e6),
                                      trace.Op(dev, "run 2", "", 8e6, 4e6)],
                     spans=[], window=(0.0, 10e6), devices=[dev])
    assert trace.busy_ns(tr) == 6e6
    assert [m.name for m in trace.whole_runs(tr)] == ["run 1"]
    assert reader("rlc_batch_ms")({"trace": tr}) == 4.0


def test_scoped_time_lies_inside_the_program_runs(tpu_trace):
    in_scopes = trace.op_time_ns(tpu_trace, ("cst.epoch_sweep",
                                             "cst.balances_list_root"))
    runs = sum(m.dur_ns for m in tpu_trace.modules)
    assert 0 < in_scopes <= runs


def test_busy_and_idle_add_up(tpu_trace):
    busy = trace.busy_ns(tpu_trace)
    gaps = sum(g for _, g in trace.idle_gaps(tpu_trace, "/device:TPU:0"))
    assert 0 < busy < trace.window_ns(tpu_trace)
    assert busy + gaps == pytest.approx(trace.window_ns(tpu_trace))


def test_breakdown_labels_idle_by_host_span(tpu_trace):
    b = trace.breakdown(tpu_trace)
    assert 0 < len(b["device_ops"]) <= 10
    assert b["idle_gaps"][0][0] == "bench.submit"
    assert b["idle_gaps"][0][1] >= 0.019


def test_program_runs_from_a_host_only_tpu_trace():
    """A verify-agg-backlog window on one TPU v5 lite, traced host-only:
    the RLC program's runs come from the runtime's enqueue and completion
    events, the second run queued behind the first."""
    tr = trace.read(str(FIXTURES / "tpu_host_only.xplane.pb"))
    assert not tr.ops and tr.devices == ["/device:TPU:0"]
    runs = sorted(tr.modules, key=lambda m: m.start_ns)
    assert len(runs) == 7
    assert runs[1].start_ns == runs[0].start_ns + runs[0].dur_ns
    assert all(2.25e9 < m.dur_ns < 2.27e9 for m in runs)
    assert sum(s.name == "bench.submit" for s in tr.spans) == 3072
    assert 70 < trace.idle_share(tr) < 80


def test_union_of_overlapping_intervals():
    assert trace.union_ns([(0, 5), (3, 4), (10, 1), (10, 0.5)]) == 8


def test_self_time_leaves_out_nested_ops():
    dev = "/device:TPU:0"
    ops = [trace.Op(dev, "while.1", "a/cst.x", 0, 10),
           trace.Op(dev, "fusion.1", "a/cst.x", 1, 3),
           trace.Op(dev, "fusion.2", "a/cst.x", 5, 4),
           trace.Op(dev, "fusion.3", "a/cst.y", 12, 2)]
    assert trace.self_times(ops) == [3, 3, 4, 2]
    tr = trace.Trace(ops, [], [], (0, 20), [dev])
    assert trace.op_time_ns(tr, ("cst.x",)) == 10


def test_instruction_name_of_a_tpu_op():
    assert trace.instruction(
        "%fusion.4123 = s32[512,2,33]{2,0,1} fusion(s32[2] %x), "
        "kind=kLoop") == "fusion.4123"


def test_sweep_ms():
    ctx = {"trace": synthetic(), "counters": {"scopes": SCOPES}}
    assert reader("sweep_ms")(ctx) == 1.5


def test_merkle_ms():
    ctx = {"trace": synthetic(), "counters": {"scopes": SCOPES}}
    assert reader("merkle_ms")(ctx) == 0.5


def test_sweep_roofline():
    ctx = {"trace": synthetic(),
           "counters": {"scopes": SCOPES, "sweep_bytes_per_step": 819},
           "peaks": {"hbm_bytes_per_s": 819e9}}
    # 1 ns of least time over 1.5 ms per step
    assert reader("sweep_roofline")(ctx) == pytest.approx(1e-4 / 1.5)
    ctx["peaks"] = None
    assert reader("sweep_roofline")(ctx) is None


@pytest.mark.parametrize("name", ["device_idle.epoch", "device_idle.verify"])
def test_device_idle(name):
    # ops cover 1..5 ms of a 10 ms window
    assert reader(name)({"trace": synthetic()}) == pytest.approx(60.0)


def test_rlc_batch_ms():
    assert reader("rlc_batch_ms")({"trace": synthetic()}) == 4.0


def test_submit_ms():
    assert reader("submit_ms")({"trace": synthetic()}) == 3.0


def test_busy_falls_back_to_program_runs():
    tr = synthetic()._replace(ops=[])
    assert trace.busy_ns(tr) == 4e6


def test_statements_per_batch():
    read = reader("statements_per_batch")
    assert read({"counters": {"settled": 1024, "batches": 2}}) == 512
    assert read({"counters": {"settled": 0, "batches": 0}}) is None


def test_readers_find_nothing_in_an_empty_trace():
    empty = trace.Trace([], [], [], (0.0, 1e6), [])
    ctx = {"trace": empty, "counters": {"scopes": SCOPES,
                                        "sweep_bytes_per_step": 1},
           "peaks": {"hbm_bytes_per_s": 1.0}}
    for name in ("sweep_ms", "merkle_ms", "sweep_roofline",
                 "device_idle.epoch", "rlc_batch_ms", "submit_ms"):
        assert reader(name)(ctx) is None, name
