"""Fork-choice subsystem tests — proto-array store vs the phase0 spec
oracle, the fc_rung ladders, async facades, the serve `head` lane with
its breaker fallback arc, and the synthetic workload the serve lane drives.

Parity contract: every head the device kernels pick must be
bit-identical to THE SPEC's `get_head` over a Store synthesized from
the same facts (`forkchoice.oracle`), and the store's batched
latest-message fold must match the spec's sequential
`update_latest_messages` message-for-message.  The spec-store-driven
mirror (real blocks through on_block) lives in
tests/phase0/fork_choice/test_device_store.py.
"""

import numpy as np
import pytest

from consensus_specs_tpu.forkchoice import (
    FC_BATCH_STEPS,
    FC_BLOCK_STEPS,
    FC_VALIDATOR_STEPS,
    ProtoArrayStore,
    fc_rung,
)
from consensus_specs_tpu.forkchoice import kernels as fc_kernels
from consensus_specs_tpu.forkchoice import oracle as fc_oracle
from consensus_specs_tpu.serve.futures import DeviceFuture

GWEI_32 = 32 * 10 ** 9


def _root(tag: int) -> bytes:
    return bytes([tag]) + b"\x07" * 31


def _store(n_validators=16, anchor=None, **kw):
    kw.setdefault("slots_per_epoch", 8)
    kw.setdefault("preset", "minimal")
    st = ProtoArrayStore(anchor or _root(1), 0, **kw)
    if n_validators:
        st.set_validators(np.full(n_validators, GWEI_32,
                                  dtype=np.int64))
    return st


def _random_store(seed, n_blocks=18, n_validators=40):
    """Seeded random tree + message batches + boost/equivocation mix —
    the randomized parity generator."""
    rng = np.random.RandomState(seed)
    anchor = bytes([seed % 256]) + b"\x00" * 31
    st = ProtoArrayStore(anchor, 0, slots_per_epoch=8, preset="minimal")
    roots = [anchor]
    for i in range(1, n_blocks):
        parent = roots[rng.randint(0, i)]
        slot = st.slots[st.root_index[parent]] + 1 + rng.randint(0, 2)
        root = bytes(rng.randint(0, 256, 32, dtype=np.uint8))
        st.add_block(root, parent, slot, 0, 0)
        roots.append(root)
    eb = np.full(n_validators, GWEI_32, dtype=np.int64)
    eb[rng.randint(0, n_validators, 4)] = 31 * 10 ** 9
    active = np.ones(n_validators, bool)
    active[rng.randint(0, n_validators, 2)] = False
    slashed = np.zeros(n_validators, bool)
    slashed[rng.randint(0, n_validators, 2)] = True
    st.set_validators(eb, active=active, slashed=slashed)
    st.set_current_epoch(max(st.slots) // 8 + 1)
    for _ in range(3):
        k = rng.randint(1, 24)
        st.apply_attestations(
            rng.randint(0, n_validators, k).tolist(),
            rng.randint(0, 4, k).tolist(),
            [roots[rng.randint(0, n_blocks)] for _ in range(k)])
    if seed % 2:
        st.set_proposer_boost(roots[rng.randint(1, n_blocks)])
    if seed % 3 == 0:
        st.mark_equivocators(rng.randint(0, n_validators, 2).tolist())
    return st, roots


# --- rung ladders -------------------------------------------------------------


def test_fc_rung_ladders():
    assert fc_rung(0) == 1 or fc_rung(0) == FC_BLOCK_STEPS[0]
    assert fc_rung(1) == FC_BLOCK_STEPS[0]
    assert fc_rung(64) == 64
    assert fc_rung(65) == 1024
    assert fc_rung(1024) == 1024
    assert fc_rung(5000) == 16384
    assert fc_rung(40000) == 65536          # pow2 past the ladder top
    assert fc_rung(100, FC_VALIDATOR_STEPS) == 256
    assert fc_rung(300, FC_VALIDATOR_STEPS) == 4096
    assert fc_rung(2, FC_BATCH_STEPS) == 64


def test_rung_ladder_shape_sharing():
    """Different live batch sizes inside one rung share the compiled
    kernel (the lru-cached factory is keyed on the padded shapes)."""
    st = _store(n_validators=16)
    st.add_block(_root(2), _root(1), 1, 0, 0)
    st.apply_attestations([0], [1], [_root(2)])
    before = fc_kernels._apply_kernel.cache_info()
    for k in (1, 3, 17, 50):       # all land on the 64-batch rung
        st.apply_attestations([i % 16 for i in range(k)], [2] * k,
                              [_root(2)] * k)
    after = fc_kernels._apply_kernel.cache_info()
    assert after.currsize == before.currsize
    assert after.misses == before.misses


# --- randomized parity vs the spec oracle ------------------------------------


def test_randomized_tree_parity_vs_spec_oracle():
    for seed in range(8):
        st, _ = _random_store(seed)
        dev = st.get_head()
        host = st.get_head_host()
        assert dev == host, (seed, dev.hex(), host.hex())


def test_tie_break_lexicographic():
    """Two zero-weight siblings: the larger root wins, exactly like
    the oracle's bytes compare (the 8-limb refinement)."""
    st = _store()
    a, b = _root(0x0A), _root(0x0B)
    st.add_block(a, _root(1), 1, 0, 0)
    st.add_block(b, _root(1), 1, 0, 0)
    st.set_current_epoch(1)
    assert st.get_head() == max(a, b) == st.get_head_host()
    # a single vote for the smaller root overrides the tie-break
    st.apply_attestations([0], [1], [min(a, b)])
    assert st.get_head() == min(a, b) == st.get_head_host()


def test_ex_ante_boost_and_expiry():
    """Proposer boost shields the timely block from one adversarial
    attestation; dropping the boost re-orgs back (the ex-ante arc)."""
    st = _store(n_validators=64)
    withheld, timely = _root(0x0B), _root(0x0C)
    st.add_block(withheld, _root(1), 1, 0, 0)
    st.add_block(timely, _root(1), 2, 0, 0)
    st.set_current_epoch(1)
    st.apply_attestations([0], [0], [withheld])
    st.set_proposer_boost(timely)
    assert st.get_head() == timely == st.get_head_host()
    st.set_proposer_boost(None)
    assert st.get_head() == withheld == st.get_head_host()


def test_viability_filters_stale_voting_source():
    """A heavier branch whose voting-source epoch is stale (more than
    two epochs behind) is filtered out of the walk, device and oracle
    alike."""
    st = ProtoArrayStore(_root(1), 0, slots_per_epoch=8,
                         justified_epoch=5, preset="minimal")
    st.set_current_epoch(9)
    good, stale = _root(0x21), _root(0xFE)
    st.add_block(good, _root(1), 41, 5, 5)
    st.add_block(stale, _root(1), 42, 2, 2)
    st.set_checkpoints(5, _root(1), 0, _root(1))
    st.set_validators(np.full(8, GWEI_32, dtype=np.int64))
    st.apply_attestations([0, 1, 2], [8, 8, 8], [stale] * 3)
    assert st.get_head() == good == st.get_head_host()


def test_finalized_descent_filter():
    """With a non-genesis finalized checkpoint, leaves that do not
    descend from the finalized root drop out of the viable tree."""
    st = ProtoArrayStore(_root(1), 0, slots_per_epoch=8,
                         justified_epoch=1, preset="minimal")
    fin, other = _root(0x0F), _root(0x0E)
    st.add_block(fin, _root(1), 8, 1, 1)       # epoch-1 boundary block
    st.add_block(other, _root(1), 9, 1, 1)     # competing branch
    inside = _root(0x1F)
    st.add_block(inside, fin, 10, 1, 1)
    st.set_checkpoints(1, _root(1), 1, fin)
    st.set_current_epoch(2)
    st.set_validators(np.full(8, GWEI_32, dtype=np.int64))
    # the non-descending branch is heavier but unviable
    st.apply_attestations([0, 1, 2, 3], [1, 1, 1, 1], [other] * 4)
    assert st.get_head() == inside == st.get_head_host()


# --- the batched fold vs the spec's sequential rule ---------------------------


def test_batched_fold_matches_spec_sequential():
    """One batch with duplicate validators, epoch ties and stale
    epochs folds to EXACTLY the table the spec's sequential
    update_latest_messages produces."""
    st = _store(n_validators=8)
    a, b = _root(0x0A), _root(0x0B)
    st.add_block(a, _root(1), 1, 0, 0)
    st.add_block(b, _root(1), 2, 0, 0)
    st.set_current_epoch(1)
    st.apply_attestations([3], [2], [a])    # pre-existing message
    idx = [0, 0, 1, 1, 3, 5, 5, 3]
    ep = [1, 2, 3, 3, 1, 4, 5, 2]
    roots = [a, b, a, b, b, a, b, b]
    expected = fc_oracle.spec_apply_messages(st, idx, ep, roots)
    st.apply_attestations(idx, ep, roots)
    st._sync_pending()
    got = {int(v): (int(st._lm_epoch[v]),
                    st.roots[int(st._lm_block[v])])
           for v in range(8) if st._lm_block[v] >= 0}
    assert got == expected
    # validator 1's epoch-3 tie: the FIRST arrival (vote for a) wins,
    # exactly the sequential strictly-greater outcome
    assert got[1] == (3, a)


def test_apply_idempotent_under_retry():
    """Re-applying a batch is a no-op (the serve retry ladder's
    safety): zero newly accepted, weights and head unchanged."""
    st = _store(n_validators=8)
    a = _root(0x0A)
    st.add_block(a, _root(1), 1, 0, 0)
    st.set_current_epoch(1)
    assert st.apply_attestations([0, 1], [1, 1], [a, a]) == 2
    w_before = st.node_weights_host().tolist()
    assert st.apply_attestations([0, 1], [1, 1], [a, a]) == 0
    assert st.node_weights_host().tolist() == w_before
    assert st.get_head() == a == st.get_head_host()


def test_equivocators_frozen_and_discounted():
    st = _store(n_validators=8)
    a, b = _root(0x0A), _root(0x0B)
    st.add_block(a, _root(1), 1, 0, 0)
    st.add_block(b, _root(1), 2, 0, 0)
    st.set_current_epoch(1)
    st.apply_attestations([0, 1], [1, 1], [min(a, b), min(a, b)])
    assert st.get_head() == min(a, b)
    st.mark_equivocators([0, 1])
    # weight discounted -> zero-weight tie-break decides
    assert st.get_head() == max(a, b) == st.get_head_host()
    # frozen: later messages from equivocators are ignored
    assert st.apply_attestations([0], [3], [min(a, b)]) == 0
    assert st.get_head() == max(a, b) == st.get_head_host()


def test_host_mirror_survives_degraded_spell():
    """Device applies, then degraded-mode host applies, then device
    again: one store state, bit-equal on both routes (the breaker
    re-close path rebuilds the device arrays from the mirror)."""
    st = _store(n_validators=16)
    a, b = _root(0x0A), _root(0x0B)
    st.add_block(a, _root(1), 1, 0, 0)
    st.add_block(b, _root(1), 2, 0, 0)
    st.set_current_epoch(1)
    st.apply_attestations([0, 1], [1, 1], [a, a])          # device
    assert st.apply_attestations_host([2, 3, 4], [1, 1, 1],
                                      [b, b, b]) == 3     # degraded
    st.apply_attestations([5], [1], [b])                   # device again
    assert st.get_head() == b == st.get_head_host()
    w = st.node_weights_host()
    assert w[st.root_index[a]] == 2 * GWEI_32
    assert w[st.root_index[b]] == 4 * GWEI_32


def test_fingerprint_tracks_state():
    st, roots = _random_store(3)
    f1 = st.fingerprint()
    assert st.fingerprint() == f1          # read-only: stable
    st.apply_attestations([0], [9], [roots[1]])
    assert st.fingerprint() != f1          # any fold moves it
    f2 = st.fingerprint()
    st.set_proposer_boost(roots[2])
    assert st.fingerprint() != f2


def test_spec_oracle_memo_transparent():
    """The conftest session memo over oracle.spec_get_head (keyed on
    the store fingerprint) must be invisible: repeated evaluation hits
    the cache, a mutation misses it."""
    st, roots = _random_store(5)
    wrapped = fc_oracle.spec_get_head
    h1 = st.get_head_host()
    hits_before = getattr(wrapped, "hits", None)
    assert st.get_head_host() == h1
    if hits_before is not None:        # running under the conftest memo
        assert wrapped.hits == hits_before + 1
    st.apply_attestations([0], [9], [roots[1]])
    assert st.get_head_host() == st.get_head()


# --- async facade contract ----------------------------------------------------


def test_async_facades_settle_and_error():
    st = _store(n_validators=8)
    a = _root(0x0A)
    st.add_block(a, _root(1), 1, 0, 0)
    st.set_current_epoch(1)
    fut = st.apply_attestations_async([0, 1, 0], [1, 1, 1], [a, a, a])
    assert isinstance(fut, DeviceFuture)
    mask = fut.result()
    # validator 0 appears twice: only its winner row accepts
    assert mask.tolist() == [True, True, False]
    hfut = st.get_head_async()
    assert isinstance(hfut, DeviceFuture)
    assert hfut.result() == a
    # unknown roots and out-of-range validators raise eagerly (the
    # serve executor poisons exactly that handle)
    with pytest.raises(KeyError):
        st.apply_attestations_async([0], [1], [_root(0x77)])
    with pytest.raises(KeyError):
        st.apply_attestations_async([99], [1], [a])
    with pytest.raises(KeyError):
        ProtoArrayStore(_root(9), 0, preset="minimal",
                        slots_per_epoch=8).add_block(
                            _root(8), _root(7), 1, 0, 0)


def test_block_rung_regrowth_preserves_state():
    """Crossing the 64-block rung boundary rebuilds the device arrays
    from the mirror without losing weights."""
    st = _store(n_validators=8)
    prev = _root(1)
    roots = [prev]
    for i in range(70):                    # crosses 64 -> 1024
        r = bytes([2 + (i % 250)]) + i.to_bytes(2, "big") + b"\x00" * 29
        st.add_block(r, prev, i + 1, 0, 0)
        roots.append(r)
        prev = r
        if i == 10:
            st.apply_attestations([0, 1], [1, 1], [r, r])
    st.set_current_epoch(max(st.slots) // 8 + 1)
    assert st.get_head() == roots[-1] == st.get_head_host()
    w = st.node_weights_host()
    assert w[st.root_index[roots[11]]] == 2 * GWEI_32


# --- serve lane ---------------------------------------------------------------


def _serve_store():
    st = _store(n_validators=16)
    a, b = _root(0x0A), _root(0x0B)
    st.add_block(a, _root(1), 1, 0, 0)
    st.add_block(b, _root(1), 2, 0, 0)
    st.set_current_epoch(1)
    return st, a, b


def test_serve_head_lane_merged_dispatch():
    """Queued fc batches for one store fold into ONE device dispatch
    per pump; each request settles to its own accepted count and the
    head poll answers the post-fold head."""
    from consensus_specs_tpu.serve.executor import ServeExecutor

    st, a, b = _serve_store()
    ex = ServeExecutor(max_batch=8, depth=1)
    f1 = ex.submit_attestation_batch(st, [0, 1], [1, 1], [a, a])
    f2 = ex.submit_attestation_batch(st, [2, 3, 4], [1, 1, 1],
                                     [b, b, b])
    fh = ex.submit_head_request(st)
    ex.drain()
    assert f1.result() == 2 and f2.result() == 3
    assert fh.result() == b == st.get_head_host()
    # one merged fc_atts dispatch + one head dispatch
    assert ex.stats()["batches"] == 2


def test_serve_fc_poisoning_is_per_batch():
    """A batch with an unknown root poisons ITS handles only; the
    service keeps answering."""
    from consensus_specs_tpu.serve.executor import ServeExecutor

    st, a, _ = _serve_store()
    st2, a2, _ = _serve_store()
    ex = ServeExecutor(max_batch=8, depth=1)
    bad = ex.submit_attestation_batch(st, [0], [1], [_root(0x77)])
    good = ex.submit_attestation_batch(st2, [0], [1], [a2])
    ex.drain()
    assert isinstance(bad.exception(), KeyError)
    assert good.result() == 1


def test_serve_breaker_fallback_and_reclose():
    """The degraded arc: an injected device fault trips the head
    breaker, the spec oracle answers bit-identically, and the
    half-open probe re-closes onto the device path."""
    import time as _time

    from consensus_specs_tpu.resilience import faults
    from consensus_specs_tpu.resilience.policies import BreakerRegistry
    from consensus_specs_tpu.serve.executor import ServeExecutor

    st, a, b = _serve_store()
    st.apply_attestations([0], [1], [b])
    expected = st.get_head_host()
    ex = ServeExecutor(max_batch=8, depth=1,
                       breakers=BreakerRegistry(threshold=1,
                                                cooldown_s=0.05))
    faults.install({"seed": 3, "faults": [
        {"site": "dispatch", "kind": "raise", "key": "fc_head@*",
         "count": 1}]})
    try:
        f1 = ex.submit_head_request(st)
        ex.drain()
        assert f1.result() == expected       # oracle answered
        assert ex.stats()["fallbacks"] == 1
        assert ex.stats()["breakers"]["head@1"] == "open"
        _time.sleep(0.06)
        f2 = ex.submit_head_request(st)      # half-open probe
        ex.drain()
        assert f2.result() == expected
        assert ex.stats()["breakers"]["head@1"] == "closed"
    finally:
        faults.clear()


def test_serve_fc_atts_degraded_applies_on_mirror():
    """With the fc_atts breaker open, applies land on the host mirror
    and the store stays consistent when the device path returns."""
    from consensus_specs_tpu.resilience import faults
    from consensus_specs_tpu.resilience.policies import BreakerRegistry
    from consensus_specs_tpu.serve.executor import ServeExecutor

    st, a, b = _serve_store()
    ex = ServeExecutor(max_batch=8, depth=1,
                       breakers=BreakerRegistry(threshold=1,
                                                cooldown_s=60.0))
    faults.install({"seed": 3, "faults": [
        {"site": "dispatch", "kind": "raise", "key": "fc_weights@*",
         "count": 1}]})
    try:
        f1 = ex.submit_attestation_batch(st, [0, 1], [1, 1], [b, b])
        ex.drain()
        assert f1.result() == 2              # oracle (mirror) answered
        assert ex.stats()["fallbacks"] == 1
        # breaker still open: the next batch goes to the mirror too
        f2 = ex.submit_attestation_batch(st, [2], [1], [b])
        ex.drain()
        assert f2.result() == 1
    finally:
        faults.clear()
    # device route resumes from the mirror state
    assert st.get_head() == b == st.get_head_host()
    assert st.node_weights_host()[st.root_index[b]] == 3 * GWEI_32


def test_loadgen_schedule_carries_the_fc_lane(monkeypatch):
    """One full slot of the arrival mix submits FC_ATTS_PER_SLOT
    attestation batches against the shared store plus one head poll."""
    from consensus_specs_tpu.serve import loadgen

    class _StubEx:
        def __init__(self):
            self.kinds = []
            self.stores = []

        def submit_verify_task(self, t):
            self.kinds.append("verify")

        def submit_pairing(self, p):
            self.kinds.append("pairing")

        def submit_barycentric(self, *a):
            self.kinds.append("fr")

        def submit_sha256_root(self, *a):
            self.kinds.append("sha256")

        def submit_proof_request(self, *a):
            self.kinds.append("proof")

        def submit_das_sample(self, s):
            self.kinds.append("das")

        def submit_attestation_batch(self, store, idx, epochs, roots):
            self.kinds.append("fc_atts")
            self.stores.append(store)
            assert len(idx) == len(epochs) == len(roots)

        def submit_head_request(self, store):
            self.kinds.append("head")
            self.stores.append(store)

    monkeypatch.setattr(loadgen, "FC_ATTS_PER_SLOT", 2)
    monkeypatch.setattr(loadgen, "HEAD_POLLS_PER_SLOT", 1)
    per_slot = (loadgen.ATT_STATEMENTS_PER_SLOT
                + loadgen.SYNC_STATEMENTS_PER_SLOT
                + loadgen.KZG_EVALS_PER_SLOT
                + loadgen.SHA_ROOTS_PER_SLOT
                + loadgen.PROOF_REQUESTS_PER_SLOT
                + loadgen.DAS_SAMPLES_PER_SLOT + 3)
    sentinel = object()

    def batches():
        while True:
            yield ([0, 1], [1, 1], [b"r1", b"r2"])

    ex = _StubEx()
    submit, kinds = loadgen.make_submitter(
        ex, ["task"],
        {"pairing": None, "fr": (1, 2, 3), "sha256": (None, 1),
         "proof": (None, [0]),
         "das": ["s0"] if loadgen.DAS_SAMPLES_PER_SLOT else [],
         "fc": (sentinel, batches())})
    for _ in range(per_slot):
        submit()
    assert kinds["fc_atts"] == 2 and kinds["head"] == 1
    assert ex.kinds.count("fc_atts") == 2
    assert ex.kinds.count("head") == 1
    assert all(s is sentinel for s in ex.stores)


# --- the synthetic workload: device head vs the spec oracle -----------------


@pytest.mark.parametrize("n_blocks, n_validators, seed", [
    (48, 256, 53),      # the serve loadgen's fork-choice lane
    (64, 1024, 29),     # a validator count past the 256 rung
], ids=["loadgen_lane", "tree_64x1024"])
def test_synthetic_workload_head_matches_spec_oracle(n_blocks, n_validators,
                                                     seed):
    """`forkchoice.synthetic` (the workload the serve lane drives): after
    each of eight attestation batches the device head is the phase0
    spec's `get_head`, bit for bit."""
    import itertools

    from consensus_specs_tpu.forkchoice.synthetic import (
        attestation_stream,
        synthetic_store,
    )

    store, roots = synthetic_store(n_blocks, n_validators, seed=seed)
    batches = itertools.islice(
        attestation_stream(roots, n_validators, 64, seed=seed), 8)
    for k, batch in enumerate(batches):
        store.apply_attestations(*batch)
        assert store.get_head() == store.get_head_host(), k


# --- @slow: bigger randomized sweep ------------------------------------------


@pytest.mark.slow
def test_randomized_parity_large_rungs():
    """Randomized parity past the first rung boundaries (1024-block /
    4096-validator shapes — compile-heavy, so out of the fast tier)."""
    for seed in (21, 22):
        st, _ = _random_store(seed, n_blocks=90, n_validators=300)
        assert st.get_head() == st.get_head_host(), seed
