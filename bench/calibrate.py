"""Readings of the numbers a cell's check compares, for setting limits.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 \\
        --control-seeds 4,5,6 --faults half,altered --fault-seeds 7,8,9 \\
        --seconds 10 [--traffic '{"instances": 1}'] [--rehearse]

Runs the cell once per seed with the program (the lower readings), then
once per control seed with the control in the program's place: the
plain reference with its cross terms one precision below the
configuration's (``reference.cross``, ``bf16_3x``), which the check has
to find wrong (the upper readings), then once per fault seed with each
of ``faults.py``'s named faults planted in the program. ``--traffic``
overrides parameters of the mix. All runs share one process, so the chip is held once. Prints one JSON line per
run: the side, the seed, each compared number, the end-to-end metrics
and the driver's info.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys

import faults
import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--faults", default="",
                    help="faults of faults.py to plant, one at a time")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--traffic", default="{}")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    split = lambda text: [s for s in text.split(",") if s]  # noqa: E731
    sides = [("program", s) for s in split(args.seeds)] + \
        [("control", s) for s in split(args.control_seeds)] + \
        [(f"fault:{f}", s) for f in split(args.faults)
         for s in split(args.fault_seeds)]
    traffic = json.loads(args.traffic)
    kind = run.cell_spec(args.workload, args.rehearse)[2]["kind"]
    for side, seed in sides:
        argv = ["--workload", args.workload, "--seed", seed,
                "--seconds", str(args.seconds)]
        if args.rehearse:
            argv.append("--rehearse")
        with contextlib.ExitStack() as stack:
            if side.startswith("fault:"):
                stack.enter_context(faults.planted(kind, side[6:]))
            res = run.run(argv, control=side == "control", traffic=traffic)
        print(json.dumps({"side": side,
                          "seed": int(seed),
                          "numbers": {k: c["value"] for k, c in
                                      res["checks"].items()},
                          "correct": res["correct"],
                          "metrics": {k: m["value"] for k, m in
                                      res["metrics"].items()},
                          "memory_peak_bytes":
                              res["device"]["memory_peak_bytes"],
                          "info": res["info"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
