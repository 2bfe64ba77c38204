"""The committee cell's code path on the CPU at a tiny size, past the look
for a chip, and its new readers on synthetic traces.  The RLC kernel is
answered on the host (`host_kernel`), after the program's committee
aggregation program has run on the CPU: a sound run reads `correct`, and
each control and a fault planted after the registry's upload read not
correct."""

import random

import pytest

from benchmark import trace
from benchmark.reference import committees
from benchmark.tests.test_rehearsal import host_kernel  # noqa: F401
from benchmark.tests.test_rehearsal import SEED, rehearse
from benchmark.tests.test_trace import reader


def committee_cell():
    from benchmark import run

    loaded = run.load_cell("verify-committee-backlog")
    loaded["config"].update(validators=1024, committees_per_slot=2,
                            committee_size=16)
    loaded["traffic"].update(outstanding=8, statements_per_message=4,
                             pool_statements=16, warmup_statements=4,
                             probe_statements=8, workers=1,
                             reference_sample=2, registry_sample=4)
    return loaded


@pytest.fixture
def committee_kernel(host_kernel, monkeypatch):  # noqa: F811
    """`host_kernel` behind the program's committee aggregation: a batch
    with `pubkeys` runs the aggregation program on the CPU, reads its
    aggregates back and hands them to the host kernel as the tasks' keys;
    the executor's per-statement recheck is the oracle's pairing check,
    each statement checked once."""
    from consensus_specs_tpu.ops import bls_batch
    from consensus_specs_tpu.ops.bls import ciphersuite, curve
    from consensus_specs_tpu.serve.futures import DeviceFuture

    def kernel(tasks, rng=None, pubkeys=None, **kw):
        if pubkeys is None:
            return host_kernel(tasks, rng=rng)
        pubkeys.enqueue(pubkeys.prepare(bls_batch._bucket(len(pubkeys))))
        points = pubkeys.points()
        if any(p is None for p in points):
            return DeviceFuture.settled(False)
        return host_kernel([(p, m, s) for p, (_, m, s) in
                            zip(points, tasks)], rng=rng)

    seen = {}

    def pairing_check(pairs):
        key = tuple((curve.g1_to_bytes(p), curve.g2_to_bytes(q))
                    for p, q in pairs)
        if key not in seen:
            seen[key] = ciphersuite._pairing_check(pairs)
        return seen[key]

    monkeypatch.setattr(bls_batch, "batch_verify_async", kernel)
    monkeypatch.setattr(bls_batch, "pairing_check_device", pairing_check)
    return kernel


def test_committee_sound_and_traced(committee_kernel):
    result = rehearse(committee_cell(), traced=True, seconds=4.0)
    assert result["correct"] is True, result["checks"]
    assert set(result["checks"]) == {"unanswered", "verdict_errors",
                                     "registry_errors"}
    assert result["attempted"] >= 8 + 4
    assert result["metrics"]["submit_ms"]["value"] > 0
    assert result["metrics"]["keys_per_statement"]["value"] > 1


@pytest.mark.parametrize("control", ["all_bits", "unit_coefficients",
                                     "altered_registry"])
def test_committee_control_is_refused(committee_kernel, control):
    result = rehearse(committee_cell(), control=control)
    assert result["correct"] is False
    errors = result["checks"]["verdict_errors"]["value"]
    if control == "unit_coefficients":
        # the swapped signatures pass a batch check whose coefficients
        # are 1; everything else is answered right
        assert errors == 1
        assert result["checks"]["unanswered"]["value"] == 0
    else:
        assert errors > 0


def test_committee_needs_the_program_registry(monkeypatch):
    from benchmark.systems import committee_verify

    monkeypatch.setattr(committee_verify, "REGISTRY_MODULE",
                        "consensus_specs_tpu.ops.bls_batch.no_registry")
    loaded = committee_cell()
    with pytest.raises(RuntimeError, match="no_registry"):
        committee_verify.System(loaded["config"], loaded["traffic"], SEED,
                                1.0, None)


def test_reference_registry_chain_matches_scalar_multiplication():
    coords = committees.make_registry(SEED, 64)
    picks = random.Random(1).sample(range(64), 6)
    read = [tuple(int.from_bytes(c.tobytes(), "big") for c in coords[i])
            for i in picks]
    assert committees.check_registry(SEED, picks, read) == 0
    read[2] = read[3]
    assert committees.check_registry(SEED, picks, read) == 1


def test_reference_statements_and_probe():
    coords = committees.make_registry(SEED, 256)
    table = committees.make_committees(SEED, 256, 4, 2, 16)
    stmts = committees.make_statements(SEED, table, [(0, 0), (0, 1)], 4,
                                       0.97, 0.03)
    assert len({s[2] for s in stmts[:4]}) == 4
    assert all(len(committees.decode_bitlist(s[2])) == 16 for s in stmts)
    rng = random.Random(2)
    for how in committees.TAMPERINGS:
        bad, where = committees.tamper(stmts, how, 1, rng, 2)
        assert all(i >= 4 for i in where)
        verdicts, _ = committees.verify_all([bad[i] for i in where] +
                                            stmts[:1], table, coords)
        assert verdicts == [False] * len(where) + [True]


def _runs(durations_ms):
    dev, t, out = "/device:TPU:0", 0.0, []
    for d in durations_ms:
        out.append(trace.Op(dev, f"run {len(out)}", "", t, d * 1e6))
        t += d * 1e6
    return trace.Trace([], out, [], (0.0, t), [dev])


def test_pk_aggregate_ms_reads_the_shorter_of_alternating_runs():
    read = reader("pk_aggregate_ms")
    assert read({"trace": _runs([2300, 200, 2310, 220, 2290])}) == 210.0
    assert read({"trace": _runs([200, 2300, 240, 2310])}) == 220.0


@pytest.mark.parametrize("runs", [[], [2300], [2300, 2300, 2300, 2300],
                                  [2300, 200, 200, 2300, 2310, 210],
                                  [1000, 700, 1010, 690]])
def test_pk_aggregate_ms_finds_nothing_without_two_alternating_kinds(runs):
    assert reader("pk_aggregate_ms")({"trace": _runs(runs)}) is None


def test_pk_aggregate_roofline_and_keys_per_statement():
    ctx = {"trace": _runs([2000, 100, 2000, 100]),
           "counters": {"pk_aggregate_bytes_per_batch": 8.19e7,
                        "keys_aggregated": 4820, "settled": 10},
           "peaks": {"hbm_bytes_per_s": 8.19e11}}
    # 0.1 ms at the peak over 100 ms a run
    assert reader("pk_aggregate_roofline")(ctx) == pytest.approx(0.1)
    assert reader("keys_per_statement")(ctx) == 482.0
    ctx["peaks"] = None
    assert reader("pk_aggregate_roofline")(ctx) is None
    assert reader("keys_per_statement")({"counters": {"settled": 3}}) is None
