"""Record the TPU trace fixture of the program's own spans.

    python3 benchmark/tests/fixtures/record_program_spans.py [DIR]

Run on one TPU chip.  Writes `program_spans.xplane.pb` and
`program_spans.json` into DIR, by default beside this file.  Inside the
harness's `bench.window` span: three wire-format FastAggregateVerify
submits through the program's `ServeExecutor`, each in a `bench.submit`
span (the program's `cst.serve.parse` spans and their four phases), and
one step of a small jitted program on the device.  The JSON holds the
program's own count of its spans, `telemetry.profiled_spans()`, taken
when the session ended.
"""

import glob
import json
import shutil
import sys
import tempfile
from pathlib import Path

import jax
import jax.numpy as jnp

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[2]))

from consensus_specs_tpu import telemetry  # noqa: E402
from consensus_specs_tpu.ops.bls import ciphersuite  # noqa: E402
from consensus_specs_tpu.serve import ServeExecutor  # noqa: E402


@jax.jit
def step(x):
    return x * 3 + x // 7


def main():
    if jax.devices()[0].platform != "tpu":
        sys.exit("record_program_spans: needs a TPU")
    statements = []
    for k in (3, 5, 7):
        msg = bytes([k]) * 32
        statements.append((ciphersuite.SkToPk(k), msg,
                           ciphersuite.Sign(k, msg)))
    x = jnp.arange(1 << 16, dtype=jnp.int32)
    jax.block_until_ready(step(x))
    ex = ServeExecutor()
    tmp = tempfile.mkdtemp()
    with jax.profiler.trace(tmp):
        with jax.profiler.TraceAnnotation("bench.window"):
            for pk, msg, sig in statements:
                with jax.profiler.TraceAnnotation("bench.submit"):
                    ex.submit_fast_aggregate_verify([pk], msg, sig)
            with jax.profiler.TraceAnnotation("bench.step"):
                jax.block_until_ready(step(x))
    out = Path(sys.argv[1]) if len(sys.argv) > 1 else HERE
    out.mkdir(parents=True, exist_ok=True)
    (path,) = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)
    shutil.copy(path, out / "program_spans.xplane.pb")
    (out / "program_spans.json").write_text(
        json.dumps(telemetry.profiled_spans(), indent=1) + "\n")
    shutil.rmtree(tmp)


if __name__ == "__main__":
    main()
