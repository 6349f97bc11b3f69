"""Readings that the limits of ``bench/limits/<cell>.json`` are set from.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 20

For each seed, in one process: the cell's set-up and a short window at
the cell's own load and sizes, then the harness's own comparison with
the float8 control put in the program's place (its ``checks`` and
``correct``: the control's reading, which has to come out not correct)
and, at the same positions, the program's reading (``program_*``).
Prints one JSON line per seed.  The benchmark's own runs never run the
control.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    run.use_compile_cache()
    for seed in [int(s) for s in args.seeds.split(",")]:
        res = run.run_cell(args.workload, seed, args.seconds, False,
                           control=True,
                           log=lambda *a, **k: None)
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "checks": res["checks"],
                          **res["check_detail"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
