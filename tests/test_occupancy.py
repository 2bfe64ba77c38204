"""Device-occupancy ledger contract tests
(`consensus_specs_tpu/telemetry/occupancy.py`).

Pins the pipeline-occupancy contracts a serve round leans on: the
interval arithmetic (union-merge across overlapping multi-device
dispatches), the EXACT bubble partition (busy + the four
causes sum to the measured wall to 1e-6 relative — the same contiguity
contract as reqtrace's latency components), the overlap score telling a
serialized depth-1 pipeline from a hidden depth-3 one, the disabled
path a true no-op, and the serve-block schema
(`export.validate_occupancy_block`).
"""

from __future__ import annotations

import time

import pytest

from consensus_specs_tpu import telemetry
from consensus_specs_tpu.telemetry import core, occupancy
from consensus_specs_tpu.telemetry.export import validate_occupancy_block

# busy + bubbles must sum to the wall within this RELATIVE tolerance
SUM_EPS = 1e-6


@pytest.fixture(autouse=True)
def _clean_ledger():
    saved = core._save_state()
    was = occupancy.enabled()
    occupancy.configure(enabled=True)
    occupancy.reset()
    yield
    occupancy.reset()
    occupancy.configure(enabled=was)
    core._restore_state(saved)


def _busy(t0, t1, dev="0", label="kernel:x"):
    occupancy._push(occupancy._BUSY, dev, label, t0, t1)


def _prep(t0, t1, dev="0", kind="verify"):
    occupancy._push(occupancy._PREP, dev, kind, t0, t1)


def _settle(t0, t1, dev="0", kind="verify"):
    occupancy._push(occupancy._SETTLE, dev, kind, t0, t1)


def _sum_check(b):
    total = b["busy_s"] + sum(b["bubbles_s"].values())
    assert abs(total - b["wall_s"]) <= SUM_EPS * max(b["wall_s"], 1e-12), \
        (total, b["wall_s"], b["bubbles_s"])


# --- interval arithmetic -----------------------------------------------------


def test_merge_overlapping_adjacent_and_unsorted():
    assert occupancy._merge([(3, 4), (1, 2), (1.5, 3.5)]) == [(1, 4)]
    # adjacent intervals coalesce (a <= end), disjoint ones stay split
    assert occupancy._merge([(0, 1), (1, 2), (3, 4)]) == [(0, 2), (3, 4)]
    assert occupancy._merge([]) == []


def test_subtract_and_intersect():
    assert occupancy._subtract([(0, 10)], [(2, 3), (5, 7)]) == \
        [(0, 2), (3, 5), (7, 10)]
    assert occupancy._subtract([(0, 2)], [(0, 2)]) == []
    assert occupancy._intersect([(0, 4), (6, 8)], [(3, 7)]) == \
        [(3, 4), (6, 7)]
    assert occupancy._intersect([(0, 1)], [(1, 2)]) == []


def test_overlapping_multi_device_dispatches_union_not_double_count():
    # two devices busy over overlapping walls: the union is [1, 4], not
    # the 4s sum of the two intervals — the top-level busy_frac answers
    # "was ANY device busy", per-device blocks answer each device
    _busy(1.0, 3.0, dev="0")
    _busy(2.0, 4.0, dev="1")
    b = occupancy.block(window=(0.0, 10.0))
    assert abs(b["busy_s"] - 3.0) < 1e-9
    assert abs(b["devices"]["0"]["busy_s"] - 2.0) < 1e-9
    assert abs(b["devices"]["1"]["busy_s"] - 2.0) < 1e-9
    _sum_check(b)
    # the same-batch interval reported by both seams never double-counts
    _busy(1.0, 3.0, dev="0", label="verify")
    b2 = occupancy.block(window=(0.0, 10.0))
    assert abs(b2["busy_s"] - 3.0) < 1e-9


# --- bubble attribution ------------------------------------------------------


def test_bubble_partition_all_four_causes_sum_to_wall():
    # timeline over (0, 10): prep [0,1] (unhidden), busy [1,3] + [6,7],
    # settle [3,3.5] → host_prep=1, settle_serialized=0.5,
    # queue_starved=[3.5,6]=2.5, drain=[7,10]=3, busy=3
    _prep(0.0, 1.0)
    _busy(1.0, 3.0)
    _settle(3.0, 3.5)
    _busy(6.0, 7.0)
    b = occupancy.block(window=(0.0, 10.0))
    bub = b["bubbles_s"]
    assert abs(bub["host_prep"] - 1.0) < 1e-9
    assert abs(bub["settle_serialized"] - 0.5) < 1e-9
    assert abs(bub["queue_starved"] - 2.5) < 1e-9
    assert abs(bub["drain"] - 3.0) < 1e-9
    assert abs(b["busy_s"] - 3.0) < 1e-9
    _sum_check(b)


def test_hidden_prep_is_not_a_bubble():
    # prep fully under device busy leaves no idle gap to attribute
    _busy(0.0, 4.0)
    _prep(1.0, 2.0)
    b = occupancy.block(window=(0.0, 4.0))
    assert b["bubbles_s"] == dict.fromkeys(occupancy.BUBBLE_CAUSES, 0.0)
    assert b["busy_frac"] == 1.0
    _sum_check(b)


def test_empty_window_and_empty_ledger():
    b = occupancy.block(window=(5.0, 5.0))
    assert b["wall_s"] == 0.0 and b["busy_s"] == 0.0
    b = occupancy.block(window=(0.0, 2.0))      # no events at all
    assert b["busy_frac"] == 0.0
    # with no busy interval the whole window trails the (absent) last
    # dispatch: attributed as drain, not starvation
    assert abs(b["bubbles_s"]["drain"] - 2.0) < 1e-9
    _sum_check(b)


def test_events_outside_window_are_clipped():
    _busy(0.0, 10.0)
    b = occupancy.block(window=(4.0, 6.0))
    assert abs(b["busy_s"] - 2.0) < 1e-9 and b["busy_frac"] == 1.0
    _sum_check(b)


def test_randomized_partition_is_exact():
    # deterministic pseudo-random soup of intervals on 2 devices: the
    # partition identity must hold regardless of layout
    x = 1234567
    for i in range(120):
        x = (1103515245 * x + 12345) % (2 ** 31)
        t0 = (x % 9000) / 1000.0
        x = (1103515245 * x + 12345) % (2 ** 31)
        dur = 0.001 + (x % 800) / 1000.0
        cls = (occupancy._BUSY, occupancy._PREP,
               occupancy._SETTLE)[i % 3]
        occupancy._push(cls, str(i % 2), "k", t0, t0 + dur)
    b = occupancy.block(window=(0.0, 10.0))
    _sum_check(b)
    for dev in b["devices"].values():
        dev_total = dev["busy_s"] + sum(dev["bubbles_s"].values())
        assert abs(dev_total - b["wall_s"]) <= SUM_EPS * b["wall_s"]


# --- overlap score -----------------------------------------------------------


def test_overlap_score_depth1_serialized_vs_depth3_pipelined():
    # depth-1 synthetic pipeline: prep N+1 only ever runs AFTER busy N
    # closes — nothing hides, score 0
    t = 0.0
    for _ in range(3):
        _prep(t, t + 1.0)
        _busy(t + 1.0, t + 2.0)
        t += 2.0
    b1 = occupancy.block(window=(0.0, t), depth=1)
    assert b1["depth"] == 1
    assert b1["overlap"]["score"] == 0.0
    assert b1["overlap"]["prep_s"] == pytest.approx(3.0)
    occupancy.reset()
    # depth-3: every prep runs entirely under an in-flight device wall
    _busy(0.0, 8.0)
    for k in range(3):
        _prep(1.0 + 2 * k, 2.0 + 2 * k)
    b3 = occupancy.block(window=(0.0, 8.0), depth=3)
    assert b3["overlap"]["score"] == 1.0
    assert b3["overlap"]["hidden_s"] == pytest.approx(3.0)


def test_overlap_score_null_without_prep():
    _busy(0.0, 1.0)
    b = occupancy.block(window=(0.0, 2.0))
    assert b["overlap"]["score"] is None and b["overlap"]["prep_s"] == 0.0


# --- batch-span lifecycle ----------------------------------------------------


def test_batch_span_publishes_three_intervals():
    span = occupancy.begin_batch("verify")
    span.mark_dispatch()
    span.mark_answer()
    span.mark_settled()
    span.mark_settled()                         # idempotent
    kinds = [(cls, label) for cls, _, label, _, _ in occupancy._events]
    assert (occupancy._PREP, "verify") in kinds
    assert (occupancy._BUSY, "verify") in kinds
    assert (occupancy._SETTLE, "verify") in kinds


def test_batch_span_abandon_paths():
    # prep failure: the prep wall is recorded, nothing else
    s = occupancy.begin_batch("verify")
    s.abandon()
    assert [c for c, *_ in occupancy._events] == [occupancy._PREP]
    occupancy.reset()
    # post-dispatch failure: the wait was still device wall
    s = occupancy.begin_batch("verify")
    s.mark_dispatch()
    s.abandon()
    classes = [c for c, *_ in occupancy._events]
    assert classes == [occupancy._PREP, occupancy._BUSY]


def test_note_settled_closes_open_kernel_spans():
    occupancy.note_kernel_dispatched("rlc", t0=time.perf_counter())
    occupancy.note_kernel_dispatched("msm", t0=time.perf_counter())
    assert occupancy.raw_snapshot()["open_spans"] == 2
    occupancy.note_settled()
    assert occupancy.raw_snapshot()["open_spans"] == 0
    labels = {label for _, _, label, _, _ in occupancy._events}
    assert labels == {"kernel:rlc", "kernel:msm"}


def test_open_span_clamped_to_window_end():
    t0 = time.perf_counter()
    occupancy.note_kernel_dispatched("rlc", t0=t0)
    b = occupancy.block(window=(t0, t0 + 0.5))
    assert abs(b["busy_s"] - 0.5) < 1e-9       # still executing: busy
    _sum_check(b)


# --- gating / bounds ---------------------------------------------------------


def test_disabled_is_a_true_noop():
    occupancy.configure(enabled=False)
    assert occupancy.begin_batch("verify") is None
    occupancy.note_kernel_busy("x", 0.0, 1.0)
    occupancy.note_kernel_dispatched("x")
    occupancy.note_settled()
    assert occupancy.raw_snapshot()["events"] == 0
    assert occupancy.raw_snapshot()["open_spans"] == 0
    assert occupancy.live_summary() is None
    assert occupancy.live_busy_frac() is None
    t0 = time.perf_counter()
    for _ in range(50_000):
        occupancy.note_kernel_busy("x", 0.0, 1.0)
    per_call = (time.perf_counter() - t0) / 50_000
    assert per_call < 5e-6, f"{per_call * 1e6:.2f}us/disabled call"


def test_event_cap_drops_are_counted():
    cap = occupancy._MAX_EVENTS
    try:
        occupancy._MAX_EVENTS = 3
        for i in range(5):
            _busy(float(i), i + 0.5)
        snap = occupancy.raw_snapshot()
        assert snap["events"] == 3 and snap["events_dropped"] == 2
        b = occupancy.block(window=(0.0, 5.0))
        assert b["events_dropped"] == 2
    finally:
        occupancy._MAX_EVENTS = cap


def test_reset_clears_ledger_and_open_spans():
    _busy(0.0, 1.0)
    occupancy.note_kernel_dispatched("x")
    occupancy.reset()
    snap = occupancy.raw_snapshot()
    assert snap["events"] == 0 and snap["open_spans"] == 0


def test_full_reset_restores_env_gate(monkeypatch):
    monkeypatch.delenv("CST_OCCUPANCY", raising=False)
    telemetry.reset(full=True)
    assert not occupancy.enabled()
    monkeypatch.setenv("CST_OCCUPANCY", "1")
    telemetry.reset(full=True)
    assert occupancy.enabled()


# --- schema / read sides -----------------------------------------------------


def test_block_schema_valid_and_violations_caught():
    _prep(0.0, 1.0)
    _busy(1.0, 3.0)
    _settle(3.0, 3.2)
    b = occupancy.block(window=(0.0, 4.0), depth=2)
    assert validate_occupancy_block(b) == []
    bad = dict(b, busy_s=b["busy_s"] + 1.0)     # breaks the sum identity
    assert any("wall" in p or "sum" in p
               for p in validate_occupancy_block(bad)), \
        validate_occupancy_block(bad)
    bad = dict(b, bubbles_s={"host_prep": 0.0})
    assert validate_occupancy_block(bad)
    assert validate_occupancy_block("fast") != []


def test_live_summary_and_busy_frac():
    now = time.perf_counter()
    _busy(now - 1.0, now - 0.5)
    s = occupancy.live_summary()
    assert s is not None and 0.0 < s["busy_frac"] <= 1.0
    assert set(s["bubbles_s"]) == set(occupancy.BUBBLE_CAUSES)
    # recomputed against a fresh `now`, so only approximately equal
    assert occupancy.live_busy_frac() == pytest.approx(
        s["busy_frac"], abs=0.05)


def test_chrome_events_rise_and_fall_per_merged_span():
    _busy(1.0, 2.0, dev="0")
    _busy(1.5, 3.0, dev="0")                     # merges with the first
    _busy(1.0, 2.0, dev="1")
    evs = occupancy.chrome_events(pid=1, t0=0.0)
    by_dev = {}
    for e in evs:
        assert e["ph"] == "C" and e["name"].startswith(
            "pipeline.device_busy.")
        by_dev.setdefault(e["name"], []).append(e["args"]["busy"])
    assert by_dev["pipeline.device_busy.0"] == [1, 0]    # merged: one pair
    assert by_dev["pipeline.device_busy.1"] == [1, 0]


def test_snapshot_carries_occupancy_subobject():
    _busy(0.0, 1.0)
    snap = telemetry.snapshot()
    occ = snap["occupancy"]
    assert occ["enabled"] and occ["events"] == 1
