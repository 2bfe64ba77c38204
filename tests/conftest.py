"""Test-suite bootstrap.

The suite runs on the CPU, on 8 virtual devices so that the mesh tests
(`jax.sharding.Mesh` over 8 devices) run on any machine.  The chip is
exercised by `chip_smoke.py` and `bench.py`, not by the unit suite.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# the suite runs on the CPU even where a chip is attached
jax.config.update("jax_platforms", "cpu")
# the parallel kernels carry uint64; entry points own this switch
jax.config.update("jax_enable_x64", True)

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running (real crypto) test")


def pytest_addoption(parser):
    parser.addoption(
        "--preset", action="store", default="minimal",
        help="preset to run spec tests under (minimal|mainnet)")
    parser.addoption(
        "--fork", action="store", default=None,
        help="restrict spec tests to one fork")
    parser.addoption(
        "--disable-bls", action="store_true", default=False,
        help="turn off BLS verification for speed (kept for parity)")
    parser.addoption(
        "--enable-bls", action="store_true", default=False,
        help="run ALL tests with real BLS (slow: pure-Python oracle); "
             "default keeps BLS off except @always_bls tests, like the "
             "reference's coverage runs")
    parser.addoption(
        "--bls-type", action="store", default="py",
        help="BLS backend: py | jax")


# --- session-scoped oracle reuse (the tier-1 870 s budget) ------------------
# The ROADMAP's standing trim candidate was "session-scoped spec-build
# reuse", but the benchwatch tier1-attribution table shows spec builds
# are ALREADY session-cached (`models.builder._SPEC_CACHE`: <1% of
# suite wall lands in the spec-build phase) — the budget is eaten by
# the pure-Python BLS oracle recomputing deterministic work across
# tests: hash-to-curve of repeated messages, subgroup checks of the
# same genesis pubkeys in every verify loop, and re-signing identical
# (privkey, message) pairs.  All of these are pure functions of their
# byte/int inputs, so the session scope memoizes them here, test-suite
# only — bench paths must keep measuring real oracle work, and the
# pairing check itself (the verification verdict) is never cached.


def _memo(fn, key_fn, cache=None):
    """Session memo over a pure function; `cache` may be shared across
    wrappers (the KZG layer shares one store across fork namespaces).
    Exceptions propagate uncached."""
    cache = {} if cache is None else cache

    def wrapper(*args, **kw):
        key = key_fn(*args, **kw)
        if key not in cache:
            cache[key] = fn(*args, **kw)
        else:
            wrapper.hits += 1
        return cache[key]

    wrapper.hits = 0
    wrapper.cache = cache
    wrapper.__wrapped__ = fn
    return wrapper


# KZG polynomial-commitment results are likewise pure functions of
# (trusted setup, argument bytes) — and the blob helpers' default rng
# seeds mean the SAME sample blobs recur across the deneb/electra/fulu
# corpus, each costing a ~5 s pure-Python commitment MSM per test (a
# full cells+proofs computation is >570 s; the DAS subsystem's
# residue-grouped route brought the two real-blob merkle-proof tests
# into tier-1, and this memo makes the second of them free).  The
# reuse installs at spec-build time (wrapping the builder's
# per-namespace cache layer, so every build path gets it) with a
# GLOBAL key on the preset's trusted-setup dir: deneb/electra/fulu
# namespaces of one preset share one result per blob.
#
# The 7594 verification/recovery seams joined with the DAS PR: their
# outputs are pure functions of the argument BYTES too — but the
# verify verdict additionally depends on the session's BLS switches
# (`bls_active=False` stubs the pairing True, and the jax backend
# routes through the DAS device path), so those flags join the key:
# a verdict cached from a stubbed call must never answer a
# real-pairing call.  Blob/sig verification verdicts (`verify_blob_*`,
# `Verify`) stay uncached as before.


def _bls_mode():
    from consensus_specs_tpu.ops import bls

    return (bls.bls_active, bls.backend_name())


# key functions use the spec functions' OWN parameter names: the spec
# p2p helpers call these seams with keyword arguments
def _verify_cell_batch_key(commitments_bytes, cell_indices, cells,
                           proofs_bytes):
    return (tuple(bytes(c) for c in commitments_bytes),
            tuple(int(i) for i in cell_indices),
            tuple(bytes(c) for c in cells),
            tuple(bytes(p) for p in proofs_bytes),
            _bls_mode())


def _recover_cells_key(cell_indices, cells):
    # keyed on the BLS mode like the verify seam: the jax backend routes
    # recovery through das/recover.py, so a device-route result must
    # never alias an oracle-route memo entry (and vice versa)
    return (tuple(int(i) for i in cell_indices),
            tuple(bytes(c) for c in cells),
            _bls_mode())


_KZG_MEMO_FNS = (
    ("blob_to_kzg_commitment", lambda blob: bytes(blob)),
    ("compute_kzg_proof", lambda blob, z: (bytes(blob), bytes(z))),
    ("compute_blob_kzg_proof",
     lambda blob, commitment: (bytes(blob), bytes(commitment))),
    ("compute_cells", lambda blob: bytes(blob)),
    ("compute_cells_and_kzg_proofs", lambda blob: bytes(blob)),
    ("verify_cell_kzg_proof_batch", _verify_cell_batch_key),
    ("recover_cells_and_kzg_proofs", _recover_cells_key),
)


@pytest.fixture(autouse=True, scope="session")
def _session_kzg_reuse():
    from consensus_specs_tpu.models import builder

    orig_install = builder._install_caches
    shared: dict = {}

    def install_with_kzg_memo(ns):
        orig_install(ns)
        setup_dir = ns.get("TRUSTED_SETUPS_DIR")
        for name, key_fn in _KZG_MEMO_FNS:
            if name in ns:
                ns[name] = _memo(
                    ns[name],
                    (lambda kf, nm: lambda *a, **kw:
                     (setup_dir, nm, kf(*a, **kw)))(key_fn, name),
                    cache=shared)

    builder._install_caches = install_with_kzg_memo
    try:
        yield
    finally:
        builder._install_caches = orig_install


@pytest.fixture(autouse=True, scope="session")
def _session_oracle_reuse():
    from consensus_specs_tpu.ops.bls import ciphersuite, hash_to_curve

    h2g2 = _memo(hash_to_curve.hash_to_g2,
                 lambda msg, dst=hash_to_curve.DST_G2:
                 (bytes(msg), bytes(dst)))
    patches = [
        # both refs: ciphersuite imported hash_to_g2 by value
        (hash_to_curve, "hash_to_g2", h2g2),
        (ciphersuite, "hash_to_g2", h2g2),
        (ciphersuite, "Sign",
         _memo(ciphersuite.Sign,
               lambda sk, msg: (int(sk), bytes(msg)))),
        (ciphersuite, "SkToPk",
         _memo(ciphersuite.SkToPk, lambda sk: int(sk))),
        # point parse + subgroup check, keyed by the wire bytes
        # (successes only: a ValueError falls through uncached)
        (ciphersuite, "_pk_to_point",
         _memo(ciphersuite._pk_to_point, lambda b: bytes(b))),
        (ciphersuite, "_sig_to_point",
         _memo(ciphersuite._sig_to_point, lambda b: bytes(b))),
    ]
    originals = [(mod, name, getattr(mod, name))
                 for mod, name, _ in patches]
    for mod, name, wrapped in patches:
        setattr(mod, name, wrapped)
    try:
        yield
    finally:
        for mod, name, orig in originals:
            setattr(mod, name, orig)


# The fork-choice spec-oracle route (`forkchoice.oracle.spec_get_head`)
# synthesizes a full executable-spec Store and runs the oracle's
# get_head — a pure function of the proto store's host state, which
# `ProtoArrayStore.fingerprint()` digests canonically (blocks,
# messages, balances, checkpoints, boost, config).  The parity suites
# re-evaluate identical store states across tests (every device head
# check re-asks the oracle), so the session scope memoizes the seam on
# the fingerprint — bench paths measure the unwrapped oracle.


@pytest.fixture(autouse=True, scope="session")
def _session_forkchoice_oracle_reuse():
    from consensus_specs_tpu.forkchoice import oracle as fc_oracle

    wrapped = _memo(fc_oracle.spec_get_head,
                    lambda proto: proto.fingerprint())
    orig = fc_oracle.spec_get_head
    fc_oracle.spec_get_head = wrapped
    try:
        yield
    finally:
        fc_oracle.spec_get_head = orig


@pytest.fixture(autouse=True, scope="session")
def _configure_backends(request):
    from consensus_specs_tpu.ops import bls
    from consensus_specs_tpu.testlib import context

    if not request.config.getoption("--enable-bls"):
        bls.bls_active = False
    bls.use_backend(request.config.getoption("--bls-type"))
    context.DEFAULT_TEST_PRESET = request.config.getoption("--preset")
    context.DEFAULT_FORK_RESTRICTION = request.config.getoption("--fork")
    yield


# --- telemetry attribution (CST_TELEMETRY=1 runs only) ----------------------
# Each test runs under a span named by its nodeid, so the end-of-session
# snapshot attributes wall time per test — the tier-1 870s-budget
# overrun (ROADMAP) gets per-test data on every CI run, alongside
# pytest's own --durations output.
#
# Each test's wall is additionally split into two phase-tagged events,
# "<nodeid> [spec-build]" vs "<nodeid> [test-body]", by reading the
# builder's cumulative `spec.build` span before and after the test: the
# benchwatch attribution table (telemetry.report) uses the split to
# name which slow tests are really paying for spec namespace builds —
# the ROADMAP's trim-target question (session compile-cache reuse,
# redundant spec builds) — and which spend the time in the test body.

_session_t0 = None
_test_count = 0


def pytest_sessionstart(session):
    global _session_t0
    import time

    _session_t0 = time.perf_counter()


@pytest.fixture(autouse=True)
def _telemetry_test_span(request):
    import time

    from consensus_specs_tpu import telemetry

    if not telemetry.enabled():
        yield
        return
    global _test_count
    _test_count += 1
    nodeid = request.node.nodeid
    build0 = telemetry.span_seconds("spec.build")
    t0 = time.perf_counter()
    with telemetry.span(nodeid):
        yield
    dur = time.perf_counter() - t0
    # spec builds triggered by THIS test (cache misses inside its span);
    # clamp to the test wall — a build started by a background thread
    # must not push the body share negative
    build = min(max(telemetry.span_seconds("spec.build") - build0, 0.0),
                dur)
    telemetry.add_event(f"{nodeid} [spec-build]", build,
                        phase="spec-build", test=nodeid)
    telemetry.add_event(f"{nodeid} [test-body]", dur - build,
                        phase="test-body", test=nodeid)


def pytest_sessionfinish(session, exitstatus):
    """Write the telemetry snapshot where CST_TELEMETRY_OUT points (CI
    uploads it as an artifact; `telemetry.report` ingests it for the
    tier-1 attribution table); no-op unless telemetry is collecting."""
    out = os.environ.get("CST_TELEMETRY_OUT")
    if not out:
        return
    from consensus_specs_tpu import telemetry

    if not telemetry.enabled():
        return
    import json
    import time
    from pathlib import Path

    if _session_t0 is not None:
        # the tier-1 870s budget is checked against this (benchwatch's
        # `tier1_wall_s` metric)
        telemetry.set_meta("tier1.session_wall_s",
                           round(time.perf_counter() - _session_t0, 3))
    telemetry.set_meta("tier1.tests", _test_count)
    path = Path(out)
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(telemetry.snapshot(), indent=1) + "\n")
