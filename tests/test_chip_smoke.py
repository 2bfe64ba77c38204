"""`chip_smoke.py`'s phases, driven on the CPU at tiny sizes.

The full sizes run only on the chip (`python chip_smoke.py`); here the
same phase functions must pass on correct outputs and fail on a
corrupted root, a wrong sweep or a wrong oracle answer — the checks are
what makes the chip run's `ok` line mean something.
"""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402

N = 1024
GENESIS = 64


def test_epoch_phase_passes_at_tiny_size():
    obs = chip_smoke.epoch_phase(N, steps=1, genesis_validators=GENESIS)
    assert obs["validators"] == N and obs["steps"] == 1
    assert obs["compile_first_s"] > 0 and obs["steady_s_per_step"] > 0


@pytest.mark.parametrize("which,match", [
    (2, "balances root"),       # the device's balances root
    (3, "registry root"),       # the device's registry root
    (0, "balances root"),       # balances that no longer hash to it
    (1, "registry root"),       # effective balances likewise
])
def test_epoch_phase_fails_on_corrupted_output(monkeypatch, which, match):
    real = chip_smoke._epoch_step

    def corrupted(params):
        step = real(params)

        def flipped(*args):
            outs = list(step(*args))
            o = outs[which]
            outs[which] = o.at[0].set(o[0] ^ o.dtype.type(1))
            return tuple(outs)

        return flipped

    monkeypatch.setattr(chip_smoke, "_epoch_step", corrupted)
    with pytest.raises(AssertionError, match=match):
        chip_smoke.epoch_phase(N, steps=1, genesis_validators=GENESIS)


def test_spec_parity_catches_a_wrong_sweep(monkeypatch):
    from consensus_specs_tpu import parallel

    real = parallel.make_epoch_step

    def off_by_one(params):
        step = real(params)

        def wrong(reg, sc, length):
            bal, eff, root = step(reg, sc, length)
            return np.asarray(bal) + np.uint64(1), eff, root

        return wrong

    monkeypatch.setattr(parallel, "make_epoch_step", off_by_one)
    with pytest.raises(AssertionError, match="balances differ"):
        chip_smoke.check_sweep_against_spec(GENESIS)


def test_serve_phase_passes_at_tiny_size():
    obs = chip_smoke.serve_phase(4, 4, 1, steady_rounds=1)
    ex = obs["executor"]
    assert ex["submitted"] == ex["settled"] == 4 * 2 + 1
    assert ex["batches"] == 3
    assert ex["fallbacks"] == ex["retries"] == ex["failed"] == 0


def test_serve_phase_fails_on_a_wrong_oracle_answer(monkeypatch):
    from consensus_specs_tpu.ops.bls import ciphersuite

    real = ciphersuite.FastAggregateVerify
    calls = []

    def wrong_on_first(pubkeys, message, signature):
        calls.append(message)
        ok = real(pubkeys, message, signature)
        return (not ok) if len(calls) == 1 else ok

    monkeypatch.setattr(ciphersuite, "FastAggregateVerify", wrong_on_first)
    with pytest.raises(AssertionError, match="differ from the oracle"):
        chip_smoke.serve_phase(4, 4, 1, steady_rounds=0)


@pytest.fixture
def restored_cache_config():
    """main() sets the compile cache up in-process; the suite runs
    uncached, so put the configuration back afterwards."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_main_refuses_the_cpu(restored_cache_config, capsys):
    assert chip_smoke.main() != 0
    out, err = capsys.readouterr()
    assert "needs a TPU" in err and "'cpu'" in err
    for line in out.splitlines():
        assert '"ok"' not in line
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
