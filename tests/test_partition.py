"""Partition-rule registry + mesh-sharded flagship parity
(`parallel.partition`, on the simulated 8-host-device CPU mesh the
conftest forces via --xla_force_host_platform_device_count=8).

Contracts from the ISSUE:
- rule matching: regex precedence (first match wins), scalar skip,
  unmatched-path hard error;
- sharded-vs-single-chip bit-exactness for the registry-driven epoch
  step (full mesh AND a `device_ids` subset);
- sharded `MerkleForest` root parity vs the single-chip forest and the
  SSZ oracle, shard-local updates and proof emission included;
- sharded-MSM parity vs the single-chip kernel and the Python oracle
  (slow-marked like every RLC/MSM-compiling test);
- the `MeshVerifier` recovery ladder covering the epoch step
  (device_ids-subset fallback after a device loss).
"""

import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from consensus_specs_tpu.parallel import (
    EpochParams,
    EpochScalars,
    MerkleForest,
    RegistryArrays,
    ShardedMerkleForest,
    make_epoch_step,
    partition,
    sharded_balances_forest,
    verify_proof,
)
from consensus_specs_tpu.parallel.partition import (
    EPOCH_STATE_RULES,
    build_mesh,
    epoch_state_rules,
    epoch_step_specs,
    match_partition_rules,
    mesh_rung,
    named_tree_leaves,
    shard_tree,
    sharded_epoch_step,
)


def _rand_words(rng, n):
    return rng.randint(0, 2**32, (n, 8), dtype=np.uint64).astype(np.uint32)


def _synthetic_registry(n, seed=0):
    rng = np.random.RandomState(seed)
    far = np.uint64(2**64 - 1)
    return RegistryArrays(
        balance=rng.randint(31_000_000_000, 33_000_000_000,
                            n).astype(np.uint64),
        effective_balance=np.full(n, 32_000_000_000, np.uint64),
        slashed=rng.rand(n) < 0.01,
        activation_eligibility_epoch=np.zeros(n, np.uint64),
        activation_epoch=np.zeros(n, np.uint64),
        exit_epoch=np.full(n, far, np.uint64),
        withdrawable_epoch=np.full(n, far, np.uint64),
        is_source=rng.rand(n) < 0.95,
        is_target=rng.rand(n) < 0.9,
        is_head=rng.rand(n) < 0.85,
        inclusion_delay=rng.randint(1, 5, n).astype(np.uint64),
        proposer_index=rng.randint(0, n, n).astype(np.int32),
    )


@pytest.fixture(scope="module")
def params():
    from consensus_specs_tpu.models.builder import build_spec

    return EpochParams.from_spec(build_spec("phase0", "mainnet"))


@pytest.fixture(scope="module")
def flagship_case(params):
    """One shared flagship case (n=256) with its single-chip outputs:
    the sharded-parity and recovery-ladder tests reuse the SAME shapes
    so each mesh topology compiles exactly once for the module."""
    n = 256
    reg = _synthetic_registry(n)
    sc = EpochScalars(current_epoch=np.uint64(100_000),
                      finality_delay=np.uint64(2),
                      slashings_sum=np.uint64(32_000_000_000))
    rng = np.random.RandomState(5)
    pk = _rand_words(rng, n)
    cred = _rand_words(rng, n)
    single = make_epoch_step(params)
    s_bal, s_eff, s_root = single(reg, sc, np.uint64(n))
    return {"n": n, "reg": reg, "sc": sc, "pk": pk, "cred": cred,
            "s_bal": np.asarray(s_bal), "s_eff": np.asarray(s_eff),
            "s_root": np.asarray(s_root)}


# --- rule matching -----------------------------------------------------------


def test_named_tree_leaves_paths():
    tree = {"a": np.zeros(4), "b": RegistryArrays(
        *[np.zeros(2, np.uint64)] * len(RegistryArrays._fields))}
    names = dict(named_tree_leaves(tree))
    assert "a" in names
    assert "b/balance" in names and "b/proposer_index" in names


def test_registry_fields_all_shard_on_data_axis():
    reg = RegistryArrays(*[np.zeros((8,), np.uint64)] * 12)
    specs = match_partition_rules(EPOCH_STATE_RULES, reg)
    assert all(s == P("data") for s in specs), specs


def test_scalars_are_never_partitioned():
    sc = EpochScalars(np.uint64(1), np.uint64(2), np.uint64(3))
    assert all(s == P() for s in match_partition_rules(
        EPOCH_STATE_RULES, sc))
    # scalar skip beats any matching rule: a (1,)-shaped "balance"
    # stays unpartitioned even though the first rule matches the name
    specs = match_partition_rules(EPOCH_STATE_RULES,
                                  {"balance": np.zeros((1,))})
    assert specs["balance"] == P()


def test_rule_precedence_first_match_wins():
    rules = ((r"special_balance", P()),
             (r"balance", P("data")))
    tree = {"special_balance": np.zeros(8), "balance": np.zeros(8)}
    specs = match_partition_rules(rules, tree)
    assert specs["special_balance"] == P()
    assert specs["balance"] == P("data")
    # reversed order: the generic rule now shadows the specific one
    specs = match_partition_rules(tuple(reversed(rules)), tree)
    assert specs["special_balance"] == P("data")


def test_unmatched_path_is_a_hard_error():
    with pytest.raises(ValueError, match="mystery_array"):
        match_partition_rules(EPOCH_STATE_RULES,
                              {"mystery_array": np.zeros(8)})
    # nested path named in the error
    with pytest.raises(ValueError, match="outer/inner"):
        match_partition_rules(EPOCH_STATE_RULES,
                              {"outer": {"inner": np.zeros(8)}})


def test_mesh_rung_ladder():
    assert mesh_rung(1) == 1
    assert mesh_rung(2) == 2
    assert mesh_rung(3) == 2
    assert mesh_rung(7) == 4
    assert mesh_rung(8) == 8
    assert mesh_rung(100) == 64


def test_build_mesh_device_ids_subset():
    import jax

    devs = jax.devices()
    mesh = build_mesh(device_ids=(5, 1, 6, 2))
    assert list(mesh.devices.flat) == [devs[5], devs[1], devs[6],
                                       devs[2]]
    mesh = build_mesh(n_devices=2)
    assert list(mesh.devices.flat) == devs[:2]
    with pytest.raises(AssertionError):
        build_mesh(n_devices=3, require_pow2=True)


def test_epoch_step_specs_derive_from_rules():
    in_specs, out_specs = epoch_step_specs()
    reg_specs, sc_specs, len_spec, pk_spec, cred_spec = in_specs
    assert all(s == P("data") for s in reg_specs)
    assert all(s == P() for s in sc_specs)
    assert len_spec == P() and pk_spec == P("data") \
        and cred_spec == P("data")
    assert out_specs == (P("data"), P("data"), P(), P())


# --- sharded epoch step: bit-exactness ---------------------------------------


def test_sharded_step_bit_exact_vs_single_chip(params, flagship_case):
    c = flagship_case
    n, reg, sc = c["n"], c["reg"], c["sc"]

    mesh = build_mesh(n_devices=8, require_pow2=True)
    step = sharded_epoch_step(mesh, params)
    rules = epoch_state_rules()
    leaves = shard_tree(mesh, {"pubkey_root": c["pk"],
                               "credentials": c["cred"]}, rules)
    m_bal, m_eff, m_broot, m_rroot = step(
        shard_tree(mesh, reg, rules), sc, np.uint64(n),
        leaves["pubkey_root"], leaves["credentials"])

    np.testing.assert_array_equal(np.asarray(m_bal), c["s_bal"])
    np.testing.assert_array_equal(np.asarray(m_eff), c["s_eff"])
    np.testing.assert_array_equal(np.asarray(m_broot), c["s_root"])

    # a device_ids SUBSET mesh (the recovery ladder's shrunken form)
    # lands the identical arrays and roots.  (0, 1, 2, 3) at the same
    # n is exactly the executable the recovery-ladder test's mesh_rung
    # trim reuses (lru cache + same shapes — one compile per module);
    # permuted device orders are pinned cheaply by
    # test_build_mesh_device_ids_subset
    step4 = partition.partitioned_epoch_step(params,
                                             device_ids=(0, 1, 2, 3))
    mesh4 = build_mesh(device_ids=(0, 1, 2, 3))
    leaves4 = shard_tree(mesh4, {"pubkey_root": c["pk"],
                                 "credentials": c["cred"]}, rules)
    out4 = step4(shard_tree(mesh4, reg, rules), sc, np.uint64(n),
                 leaves4["pubkey_root"], leaves4["credentials"])
    np.testing.assert_array_equal(np.asarray(out4[0]), c["s_bal"])
    np.testing.assert_array_equal(np.asarray(out4[2]), c["s_root"])
    np.testing.assert_array_equal(np.asarray(out4[3]),
                                  np.asarray(m_rroot))


def test_epoch_step_recovery_ladder_covers_epoch_step(params,
                                                     flagship_case):
    """The device_ids-subset fallback for the FLAGSHIP step: a lost
    device re-buckets the same epoch state over the surviving
    `mesh_rung` subset and lands bit-identical outputs."""
    from consensus_specs_tpu.resilience.faults import MeshDeviceLost
    from consensus_specs_tpu.resilience.mesh import (
        sharded_epoch_verifier)

    c = flagship_case
    v = sharded_epoch_verifier(params, n_devices=8,
                               readmit_cooldown_s=1e9)
    real = v._dispatch_fn
    calls = {"n": 0, "ids": []}

    def flaky(payload, rng_, ids):
        calls["n"] += 1
        calls["ids"].append(tuple(ids))
        if calls["n"] == 1:
            raise MeshDeviceLost("dispatch", "test", "device_loss")
        return real(payload, rng_, ids)

    v._dispatch_fn = flaky
    out = v.dispatch((c["reg"], c["sc"], np.uint64(c["n"]), c["pk"],
                      c["cred"]))
    np.testing.assert_array_equal(np.asarray(out[0]), c["s_bal"])
    np.testing.assert_array_equal(np.asarray(out[1]), c["s_eff"])
    np.testing.assert_array_equal(np.asarray(out[2]), c["s_root"])
    assert v.redispatches == 1
    assert len(v.state.lost) == 1
    assert v.lost_statements == 0
    # first attempt saw the full mesh, the retry only survivors
    assert len(calls["ids"][0]) == 8 and len(calls["ids"][1]) == 7
    # the dispatcher trims survivors to the mesh_rung power of two
    assert mesh_rung(7) == 4


# --- sharded MerkleForest ----------------------------------------------------


def test_sharded_forest_root_parity_vs_single_chip():
    n = 300                              # non-pow2 chunk count
    rng = np.random.RandomState(11)
    words = _rand_words(rng, n)
    sf = ShardedMerkleForest(words, 10, n, n_shards=8)
    f = MerkleForest(words, 10, n)
    assert sf.root_bytes() == f.root_bytes()
    assert sf.n_shards == 8
    assert sf.data_depth == f.data_depth


def test_sharded_forest_update_parity():
    n = 256
    rng = np.random.RandomState(13)
    words = _rand_words(rng, n)
    sf = ShardedMerkleForest(words, 10, n, n_shards=4)
    f = MerkleForest(words, 10, n)
    for step in range(4):
        m = int(rng.randint(1, 33))
        idx = rng.choice(n, m, replace=False).astype(np.uint32)
        new = _rand_words(rng, m)
        sf.update(idx, new)
        f.update(idx, new)
        assert sf.root_bytes() == f.root_bytes(), step
    # empty update is a no-op
    root = sf.root_bytes()
    sf.update(np.zeros((0,), np.uint32), np.zeros((0, 8), np.uint32))
    assert sf.root_bytes() == root


def test_sharded_forest_accepts_rung_padded_leaves():
    """The MerkleForest.update padding convention: leaves pre-padded to
    a `_bucket` rung (LONGER than the live index set) and sentinel
    index rows must both be dropped, not desync the shard routing."""
    from consensus_specs_tpu.parallel import incremental

    n = 128
    rng = np.random.RandomState(31)
    words = _rand_words(rng, n)
    sf = ShardedMerkleForest(words, 8, n, n_shards=4)
    f = MerkleForest(words, 8, n)
    live = np.asarray([1, 40, 127], np.uint32)
    new = _rand_words(rng, 3)
    # leaves padded to the rung, indices left at the live count
    rung = incremental._bucket(3)
    padded_leaves = np.zeros((rung, 8), np.uint32)
    padded_leaves[:3] = new
    sf.update(live, padded_leaves)
    f.update(live, new)
    assert sf.root_bytes() == f.root_bytes()
    # both pre-padded with the sentinel convention
    idx = np.full((rung,), sf.capacity, np.uint32)
    idx[:3] = [2, 41, 126]
    new2 = np.zeros((rung, 8), np.uint32)
    new2[:3] = _rand_words(rng, 3)
    sf.update(idx, new2)
    f.update(idx, new2)
    assert sf.root_bytes() == f.root_bytes()


def test_sharded_forest_single_shard_degenerates():
    n = 64
    rng = np.random.RandomState(17)
    words = _rand_words(rng, n)
    sf = ShardedMerkleForest(words, 8, n, n_shards=1)
    assert sf.root_bytes() == MerkleForest(words, 8, n).root_bytes()


def test_sharded_balances_forest_matches_ssz_oracle():
    from consensus_specs_tpu.utils.ssz.ssz_impl import hash_tree_root
    from consensus_specs_tpu.utils.ssz.ssz_typing import List, uint64

    n = 100
    rng = np.random.RandomState(19)
    bal = rng.randint(0, 2**63, n, dtype=np.uint64)
    sf = sharded_balances_forest(bal, n, limit_depth=8, n_shards=8)
    oracle = hash_tree_root(List[uint64, 1024](*(int(b) for b in bal)))
    assert sf.root_bytes() == bytes(oracle)
    # dirty update stays oracle-exact
    from consensus_specs_tpu.parallel import incremental

    dirty_val = np.asarray([0, 7, 42, 99], dtype=np.uint32)
    bal = bal.copy()
    bal[dirty_val] = rng.randint(0, 2**63, 4, dtype=np.uint64)
    chunks = incremental.dirty_chunks_from_validators(dirty_val)
    import jax.numpy as jnp

    leaves = incremental.dirty_balance_leaves(jnp.asarray(bal), chunks)
    sf.update(chunks, np.asarray(leaves))
    oracle = hash_tree_root(List[uint64, 1024](*(int(b) for b in bal)))
    assert sf.root_bytes() == bytes(oracle)


def test_sharded_forest_proofs_verify_and_track_updates():
    n = 200
    rng = np.random.RandomState(23)
    words = _rand_words(rng, n)
    sf = ShardedMerkleForest(words, 10, n, n_shards=8)
    root = sf.root_bytes()
    indices = [0, 1, 31, 32, 63, 64, 150, 199]   # spans shards
    proofs = sf.emit_proofs(indices)
    assert [p.index for p in proofs] == indices
    for p in proofs:
        assert verify_proof(p, root), p.index
        assert p.gindex == (2 << 10) + p.index
    # tampered leaf fails the branch check
    bad = proofs[3]._replace(leaf=b"\x00" * 32)
    assert not verify_proof(bad, root)
    # proofs emitted after an update verify against the NEW root only
    idx = np.asarray([32, 150], np.uint32)
    new = _rand_words(rng, 2)
    sf.update(idx, new)
    new_root = sf.root_bytes()
    fresh = sf.emit_proofs([32, 150, 0])
    assert all(verify_proof(p, new_root) for p in fresh)
    assert not verify_proof(fresh[0], root)
    # out-of-range proof index rejected
    with pytest.raises(AssertionError):
        sf.emit_proofs([n])
    # empty emission settles immediately
    assert sf.emit_proofs([]) == []


# --- sharded MSM (slow: compiles the Pippenger kernels) ----------------------


@pytest.mark.slow
def test_sharded_msm_matches_single_chip_and_oracle():
    from consensus_specs_tpu.ops.bls import curve as pc
    from consensus_specs_tpu.ops.bls_batch import (
        g1_multi_exp_device,
        g1_multi_exp_sharded,
    )

    rng = np.random.RandomState(29)
    pts = [pc.g1.mul(pc.G1_GEN, int(k))
           for k in rng.randint(1, 2**31, 8)]
    ks = [int(k) for k in rng.randint(1, 2**62, 8)]
    want = g1_multi_exp_device(pts, ks)
    got = g1_multi_exp_sharded(pts, ks, n_devices=4)
    assert pc.g1.to_affine(got) == pc.g1.to_affine(want)
    # oracle: naive sum of scalar muls
    acc = pc.g1.infinity()
    for p, k in zip(pts, ks):
        acc = pc.g1.add(acc, pc.g1.mul(p, k))
    assert pc.g1.to_affine(got) == pc.g1.to_affine(acc)
    # device_ids-subset mesh (the resilience form)
    got2 = g1_multi_exp_sharded(pts, ks, device_ids=(6, 3))
    assert pc.g1.to_affine(got2) == pc.g1.to_affine(want)
    # degenerate inputs: zero scalars / single device
    assert pc.g1.is_inf(g1_multi_exp_sharded(pts[:2], [0, 0],
                                             n_devices=4))
    one_dev = g1_multi_exp_sharded(pts[:2], ks[:2], n_devices=1)
    assert pc.g1.to_affine(one_dev) == pc.g1.to_affine(
        g1_multi_exp_device(pts[:2], ks[:2]))
