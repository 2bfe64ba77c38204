"""The main path's kernels compile for a TPU v5e chip, at real widths.

No chip is attached here: the TPU compiler compiles for a described
`v5e:2x2` topology (one of its devices), which refuses what the chip's
compiler would refuse — misaligned slices, too much fast memory, a
program that does not fit.  These guard every later PR at no chip time.
The 30-140 s whole programs (the 2**20 epoch step, the RLC kernel) are
compiled by the rehearsal recorded in CHANGES.md (PR 21), not here.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and the suite's workers all
import this file.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from consensus_specs_tpu.ops import sha256_jax
from consensus_specs_tpu.ops.bls_batch import fq, tower
from consensus_specs_tpu.parallel import epoch

# v5e HBM per chip (Google Cloud documentation, "TPU v5e")
V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.array(topo.devices), ("data",))


def _compile_fits(fn, shapes, one_chip):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes)
    assert 0 < used < V5E_HBM_BYTES, used
    return compiled


@pytest.mark.parametrize("name,fn,shapes", [
    ("fq_mul", fq.fq_mul, [((128, fq.N_LIMBS), jnp.int32)] * 2),
    ("fq12_mul", tower.fq12_mul,
     [((128, 2, 3, 2, fq.N_LIMBS), jnp.int32)] * 2),
    ("merkle_root_pow2_depth18",
     lambda w: sha256_jax.merkle_root_pow2(w, 18),
     [((1 << 18, 8), jnp.uint32)]),
])
def test_kernel_compiles_for_v5e(one_chip, name, fn, shapes):
    compiled = _compile_fits(fn, shapes, one_chip)
    out = compiled.out_info
    if name == "merkle_root_pow2_depth18":
        assert out.shape == (8,) and out.dtype == np.uint32
    else:
        assert out.shape == shapes[0][0] and out.dtype == np.int32


def test_sha256_kernel_compiles_for_v5e_at_the_records_shape(one_chip):
    """The 64-byte SHA-256 kernel at the records root's first level for
    2**20 validators: 4 * 2**20 hashes as (16, 32768, 128) word planes,
    one TPU custom call and no loop left to XLA."""
    rows = 4 * (1 << 20) // 128
    compiled = _compile_fits(sha256_jax._hash64_tiles,
                             [((16, rows, 128), jnp.uint32)], one_chip)
    assert compiled.out_info.shape == (8, rows, 128)
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert " while(" not in text


def test_records_root_is_three_kernel_calls_on_v5e(one_chip):
    """`validator_records_root` at 2**20 validators: one kernel call per
    level, no `while` loop carrying the rounds through HBM, and each call
    named under `cst.validator_records_root`, the scope `merkle_ms`
    reads."""
    from consensus_specs_tpu.parallel import merkle

    n = 1 << 20

    def records(pubkey_root, credentials, *fields):
        return merkle.validator_records_root(
            merkle.ValidatorLeaves(pubkey_root, credentials), *fields)

    shapes = ([((n, 8), jnp.uint32)] * 2 + [((n,), jnp.uint64),
                                             ((n,), jnp.bool_)]
              + [((n,), jnp.uint64)] * 4)
    compiled = _compile_fits(records, shapes, one_chip)
    assert compiled.out_info.shape == (n, 8)
    text = compiled.as_text()
    assert " while(" not in text
    kernels = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 3
    for line in kernels:
        assert "/cst.validator_records_root/" in line, line


def test_registry_fill_compiles_for_v5e(one_chip):
    """The pubkey registry's fill program at its real chunk of keys."""
    from consensus_specs_tpu.ops.bls_batch import registry

    chunk = registry.FILL_CHUNK
    compiled = _compile_fits(registry._fill_kernel(chunk),
                             [((chunk, 2, registry.COORD_BYTES), jnp.uint8)],
                             one_chip)
    x, y = compiled.out_info
    assert x.shape == y.shape == (chunk, fq.N_LIMBS)
    assert x.dtype == np.int32


@pytest.mark.parametrize("collective", ["psum", "psum_scatter"])
def test_u64_collectives_compile_for_a_v5e_mesh(mesh4, collective):
    """The sharded sweep's uint64 totals and proposer-reward scatter:
    the TPU refuses a uint64 all-reduce, so they ride uint32 limbs."""
    if collective == "psum":
        def body(x):
            return epoch._total(x, "data")
        out_spec = P()
    else:
        def body(x):
            return epoch._u64_collective(x, lambda v: lax.psum_scatter(
                v, "data", scatter_dimension=1, tiled=True))
        out_spec = P("data")
    fn = jax.shard_map(body, mesh=mesh4, in_specs=P("data"),
                       out_specs=out_spec, check_vma=False)
    x = jax.ShapeDtypeStruct((4 * 256,), jnp.uint64,
                             sharding=NamedSharding(mesh4, P("data")))
    jax.jit(fn).lower(x).compile()


def test_u64_collective_is_exact_on_the_cpu_mesh():
    devs = jax.devices()[:4]
    mesh = Mesh(np.array(devs), ("data",))
    x = np.array([2**64 - 1, 2**63 + 12345, 2**40 + 7, 3] * 4, np.uint64)
    fn = jax.jit(jax.shard_map(
        lambda v: epoch._total(v, "data"), mesh=mesh, in_specs=P("data"),
        out_specs=P(), check_vma=False))
    assert int(fn(x)) == int(x.sum(dtype=np.uint64))
