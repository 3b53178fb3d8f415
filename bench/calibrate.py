#!/usr/bin/env python3
"""Readings that set a cell's correctness limit: the program's logit
gaps and the int8 control's, on several seeds, in one process.

    python3 bench/calibrate.py --workload yi6b.decode --seeds 11,12,13 \\
        --seconds 40 --out .bench_cache/cal_yi_decode.jsonl

Each seed is a whole run of the cell (weights, engine, warm-up, a window
of ``--seconds`` at the cell's own load) whose served tokens the float32
reference recomputes; the control is the same reference with every GEMM
in int8 (``bench/models/common.py``), read at the same positions: the gap
of the token it puts first.  Both give every reading a limit can hold
(``harness.gap_readings``), and both are judged by the harness's own
comparison against the cell's limits: ``correct`` for the program,
``control_correct`` for the control, which has to come out false.  The
benchmark's own runs never run the control.
"""

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=40.0)
    p.add_argument("--out", required=True)
    args = p.parse_args(argv)
    from bench import harness
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    with open(args.out, "a") as f:
        for seed in [int(s) for s in args.seeds.split(",")]:
            r = harness.run(args.workload, seed, args.seconds, False,
                            t_process=time.monotonic(), control=True)
            row = {"workload": args.workload, "seed": seed,
                   "program": r["info"]["readings"],
                   "control": r["info"]["control_readings"],
                   "served_checked": r["info"]["served_checked"],
                   "correct": r["correct"], "compared": r["compared"],
                   "control_correct": r["info"]["control_correct"],
                   "control_compared": r["info"]["control_compared"],
                   "metrics": {k: v["value"] for k, v in r["metrics"].items()},
                   "info": r["info"]}
            f.write(json.dumps(row) + "\n")
            f.flush()
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
