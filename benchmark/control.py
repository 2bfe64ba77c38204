"""Run a cell with its control in the program's place, on several seeds.

    python3 benchmark/control.py --workload <cell> --seeds 11,12,13 \\
        --seconds <s> [--control <name>]

The controls are named by the cell's system (`System.CONTROLS`; the first
is the default): `epoch-1m` has `float32_sweep`, the sweep in float32 on
the device in place of the program's uint64 one; `verify-agg-backlog` has
`unit_coefficients`, the program's batch check with every random
coefficient 1, and `deadline_shed`, the serve executor with its deadline
shedding armed.  Each seed's run prints one JSON line with the numbers
compared and `correct`, which has to read false.  Every seed runs in this
one process, which holds the chip throughout.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmark import harness, run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one run each")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", help="one of the system's CONTROLS")
    args = ap.parse_args(argv)
    loaded = run.load_cell(args.workload)
    control = args.control or run.system_class(loaded).CONTROLS[0]
    try:
        jax, devices = harness.start_jax(loaded["cell"]["chips"])
    except harness.NoChip as exc:
        harness.log(f"control: {exc}")
        return 2
    for seed in (int(s) for s in args.seeds.split(",")):
        result = run.run(loaded, seed, args.seconds, traced=False,
                         devices=devices, control=control)
        print(json.dumps({"seed": seed, "control": control,
                          "correct": result["correct"],
                          "checks": result["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
