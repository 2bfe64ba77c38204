"""Record the TPU trace fixture the trace-reduction tests read.

    python3 benchmark/tests/fixtures/record_tpu_trace.py [DIR]

Run on one TPU chip.  Writes `tpu_trace.xplane.pb` and `tpu_trace.hlo.txt`
into DIR, by default beside this file: two steps of a small jitted program
with one scope per layer the epoch cell names, inside the harness's
`bench.window` and `bench.step` spans, and a `bench.submit` span with no
device work in it.
"""

import glob
import shutil
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp

HERE = Path(__file__).resolve().parent


@jax.jit
def step(x):
    with jax.named_scope("cst.epoch_sweep"):
        y = x * 3 + x // 7
    # keep the two scopes in ops of their own
    y = jax.lax.optimization_barrier(y)
    with jax.named_scope("cst.balances_list_root"):
        z = jnp.cumsum(y) ^ (y >> 3)
    return y, z


def main():
    if jax.devices()[0].platform != "tpu":
        sys.exit("record_tpu_trace: needs a TPU")
    # keep only the file name of the source in the program's metadata
    jax.config.update("jax_hlo_source_file_canonicalization_regex", ".*/")
    x = jnp.arange(1 << 16, dtype=jnp.int32)
    jax.block_until_ready(step(x))
    tmp = tempfile.mkdtemp()
    with jax.profiler.trace(tmp):
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(2):
                with jax.profiler.TraceAnnotation("bench.step"):
                    jax.block_until_ready(step(x))
            with jax.profiler.TraceAnnotation("bench.submit"):
                time.sleep(0.02)
    out = Path(sys.argv[1]) if len(sys.argv) > 1 else HERE
    out.mkdir(parents=True, exist_ok=True)
    (path,) = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)
    shutil.copy(path, out / "tpu_trace.xplane.pb")
    (out / "tpu_trace.hlo.txt").write_text(
        step.lower(x).compile().as_text())
    shutil.rmtree(tmp)


if __name__ == "__main__":
    main()
