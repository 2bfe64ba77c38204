"""`utils.jaxtools.enable_compile_cache`: where the persistent compile
cache lives.  The path is part of the cache's key, so it must not depend
on the host (core count, CPU flags) and must be placeable from outside
through `JAX_COMPILATION_CACHE_DIR`.  And the build ledger: JAX's trace,
lowering and compile seconds by function, each instant counted once."""

import os
import subprocess
import sys
from pathlib import Path

import pytest

import jax

from consensus_specs_tpu.utils import jaxtools

REPO = Path(__file__).resolve().parents[1]
_CACHE_KEYS = ("jax_compilation_cache_dir",
               "jax_persistent_cache_min_compile_time_secs",
               "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture
def restored_cache_config(monkeypatch):
    """Run enable_compile_cache in-process, then put the suite's cache
    configuration back (the suite itself runs uncached)."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    saved = {k: getattr(jax.config, k) for k in _CACHE_KEYS}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("cpus", [1, 8, 13, 30])
def test_default_dir_is_fixed_in_checkout(restored_cache_config,
                                          monkeypatch, cpus):
    monkeypatch.setattr(os, "cpu_count", lambda: cpus)
    jaxtools.enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir == str(REPO / ".jax_cache")
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_env_dir_is_left_to_jax_and_receives_the_entries(tmp_path):
    cache = tmp_path / "xla_cache"
    code = (
        "import jax, jax.numpy as jnp\n"
        "from consensus_specs_tpu.utils.jaxtools import "
        "enable_compile_cache\n"
        "enable_compile_cache()\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
        "jax.jit(lambda x: x * 3 + 1)(jnp.arange(4)).block_until_ready()\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache))
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == str(cache)
    assert any(cache.iterdir()), "no cache entry written"


def test_setup_failure_is_reported_not_swallowed(restored_cache_config,
                                                 monkeypatch, capsys):
    def refuse(name, value):
        raise ValueError(f"refused {name}")

    with monkeypatch.context() as m:
        m.setattr(jax.config, "update", refuse)
        jaxtools.enable_compile_cache()
    assert "compile cache not enabled: ValueError" in capsys.readouterr().err


def test_build_ledger_names_the_function_it_built():
    import jax.numpy as jnp

    def ledger_probe_kernel(x):
        return jnp.sin(x) * 3 + x

    kernel = jaxtools.jit(ledger_probe_kernel)
    before = jaxtools.build_seconds()
    kernel(jnp.arange(16.0)).block_until_ready()
    row = jaxtools.builds()["ledger_probe_kernel"]
    assert row["count"] == 1
    assert row["trace_s"] > 0 and row["lower_s"] > 0 and row["compile_s"] > 0
    after = jaxtools.build_seconds()
    for phase in ("trace_s", "lower_s", "compile_s"):
        assert after[phase] > before[phase], phase
    # a second call builds nothing
    kernel(jnp.arange(16.0)).block_until_ready()
    assert jaxtools.builds()["ledger_probe_kernel"] == row


@pytest.mark.parametrize("rows, want", [
    # disjoint builds add up
    ([("f", "trace", 0.0, 1.0), ("f", "lower", 1.0, 3.0),
      ("f", "compile", 3.0, 6.0)], (1.0, 2.0, 3.0)),
    # a function traced inside another's trace counts once
    ([("outer", "trace", 0.0, 4.0), ("inner", "trace", 1.0, 2.0)],
     (4.0, 0.0, 0.0)),
    # a constant compiled inside a trace is compile time, not trace time
    ([("outer", "trace", 0.0, 4.0), ("iota", "compile", 1.0, 2.5)],
     (2.5, 0.0, 1.5)),
    # overlapping builds on two threads: the later-started one owns the
    # overlap
    ([("a", "lower", 0.0, 3.0), ("b", "compile", 2.0, 5.0)],
     (0.0, 2.0, 3.0)),
])
def test_build_seconds_counts_each_instant_once(monkeypatch, rows, want):
    monkeypatch.setattr(jaxtools, "_builds", rows)
    got = jaxtools.build_seconds()
    assert (got["trace_s"], got["lower_s"], got["compile_s"]) == \
        pytest.approx(want)
