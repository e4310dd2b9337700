#!/usr/bin/env python3
"""Run a cell's control on the chip: the program with one guarantee of
the deployment switched off, checked by the same comparison as a
benchmark run, which has to find it not correct.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10

The deployment file names the control under ``"control"`` as settings
of ``harness.Hooks``: ``{"program_mode": "r"}`` floods without the link
gate (causal order under churn broken).
Prints each run's result line; exits 0 only if every run came out not
correct.
"""

import io
import json
import os
import sys
from contextlib import redirect_stdout

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = harness.Cell(harness.ROOT, args.workload)
    control = cell.config["control"]
    all_failed = True
    for seed in args.seeds.split(","):
        hooks = harness.Hooks()
        for key, value in control.items():
            setattr(hooks, key, value)
        out = io.StringIO()
        with redirect_stdout(out):
            rc = harness.main(["--workload", args.workload, "--seed", seed,
                               "--seconds", str(args.seconds)], hooks=hooks)
        lines = out.getvalue().strip().splitlines()
        res = json.loads(lines[-1]) if rc == 0 and lines else None
        print(json.dumps(dict(seed=int(seed), control=control, rc=rc,
                              correct=None if res is None
                              else res["correct"],
                              checks=None if res is None
                              else res["checks"])), flush=True)
        all_failed &= res is None or res["correct"] is False
    return 0 if all_failed else 1


if __name__ == "__main__":
    sys.exit(main())
