#!/usr/bin/env python3
"""Readings for the limits of ``check.py``, on the chip, at a cell's own
size: runs of one cell on several seeds in one process, with the program
under test or with the control in its place.

    python3 benchmarks/chip/control.py --workload <name> \
        --seeds 11,12,13 --seconds 5 --system control

``--system program`` gives the lower readings (what sound runs read),
``--system control`` the upper ones: the plain reference with its
crossbar at ``high`` precision (three bfloat16 passes), the nearest
precision below the ``highest`` the configuration states, put in the
program's place.  The control has to come out not correct.  Each run
prints its own result line; the last line sums them up as JSON
``{"system", "runs": [{"seed", "correct", "attempted", "failed",
"int_mismatches", "v_gap"}]}``.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import functools
import io
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip import program, run  # noqa: E402

SYSTEMS = {
    "program": None,
    "control": functools.partial(program.Reference, precision="high"),
}


def readings(root: Path, workload: str, seeds: list[int], seconds: float,
             system: str) -> list[dict]:
    runs = []
    for seed in seeds:
        buf = io.StringIO()
        run.run_cell(root, workload, seed, seconds, False,
                     system_cls=SYSTEMS[system], out=buf)
        sys.stdout.write(buf.getvalue())
        result = json.loads(buf.getvalue().strip().splitlines()[-1])
        runs.append({"seed": seed, "correct": result["correct"],
                     "attempted": result["attempted"],
                     "failed": result["failed"],
                     **{k: v["value"] for k, v in result["checks"].items()}})
    return runs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--system", choices=sorted(SYSTEMS), required=True)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    try:
        runs = readings(ROOT, args.workload, seeds, args.seconds, args.system)
    except run.RunError as e:
        print(f"control: {e}", file=sys.stderr)
        return e.code
    print(json.dumps({"system": args.system, "workload": args.workload,
                      "runs": runs}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
