"""Readings of the comparison's control on the chip, for setting its limits.

    python chipbench/control.py --workload <cell> --seconds <s> --seeds <n> [<n> ...]

For each seed, one run of the cell as ``run.py`` makes it, except that the
comparison puts the deployment's reference, computed in float32, in the
program's place.
Every compared number must then read above its limit (``correct`` false).
All seeds run in one process, so the engine compiles once.  The program's
own readings come from ordinary runs of ``run.py``.
"""
from __future__ import annotations

import argparse
import io
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    run.prepare_process()
    rows = []
    for seed in args.seeds:
        res = run.run_cell(args.workload, seed, args.seconds, False, control=True,
                           out=io.StringIO(), err=io.StringIO())
        rows.append({"seed": seed, "correct": res["correct"],
                     "checks": {k: v["value"] for k, v in res["checks"].items()}})
        print(json.dumps(rows[-1]), flush=True)
    return 0 if rows and not any(r["correct"] for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
