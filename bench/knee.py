#!/usr/bin/env python3
"""Find an open-loop cell's knee: the highest arrival rate at which the
engine keeps up, with no growing backlog.

    python3 bench/knee.py --workload yi6b.chat --seed 5 \\
        --rates 0.2,0.25,0.3 --seconds 40 --out .bench_cache/knee_yi.json

One process: the weights and the engine are made once, then each rate in
turn drives the cell's traffic mix for ``--seconds`` (the queue drained
between rates).  The backlog is the engine's queue of requests waiting
for a slot, sampled after every step; a rate is *growing* when a line
fitted to the second half of its run rises by two requests or more, or
the queue ends at four or more.  The knee is the highest rate below the
first growing one.  The result is written as the cell's
``bench/rates/<config>.<mix>.json`` content.
"""

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

GROWTH_REQS = 2.0      # fitted rise over the second half that counts
END_QUEUE = 4          # or a queue this long at the end


def growing(samples, seconds: float) -> tuple[bool, float, int]:
    """(growing?, fitted rise over the second half, queue at the end)."""
    t = np.asarray([s[0] for s in samples])
    q = np.asarray([s[1] for s in samples], float)
    half = t >= t[0] + seconds / 2 if len(t) else t
    rise = 0.0
    if half.sum() >= 3:
        slope = np.polyfit(t[half], q[half], 1)[0]
        rise = float(slope * seconds / 2)
    end = int(q[-1]) if len(q) else 0
    return (rise >= GROWTH_REQS or end >= END_QUEUE), rise, end


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--rates", required=True,
                   help="comma-separated requests/s, ascending")
    p.add_argument("--seconds", type=float, default=40.0)
    p.add_argument("--out", required=True)
    args = p.parse_args(argv)
    from bench import harness, traffic
    bench, cell, cfg_entry = harness.find_cell(args.workload)
    devices = harness.check_devices(cell["chips"])
    harness.use_compile_cache()
    c = harness.load_json(ROOT / cfg_entry["file"])
    mix = traffic.load_mix(cell["traffic"])
    eng, _ = harness.build_engine(c, args.seed)
    harness.compile_pass(eng, c["vocab_size"])
    rows, knee = [], None
    for rate in [float(r) for r in args.rates.split(",")]:
        n = traffic.count_for(mix, args.seconds, rate)
        reqs = traffic.make(mix, args.seed, n, c["vocab_size"], rate)
        win = harness.Window(eng, reqs, mix, annotate=False)
        samples = []
        step = eng.step

        def sampled_step():
            step()
            samples.append((time.monotonic(), len(eng.sched.queue)))

        eng.step = sampled_step
        t0 = time.monotonic()
        win.start(t0)
        win.run_until(t0 + args.seconds)
        eng.step = step
        grow, rise, end = growing(samples, args.seconds)
        done = len(win.done)
        toks = sum(len(lv.stamps) for lv in win.done + win.live)
        row = {"rate_req_s": rate, "growing": grow, "rise": rise,
               "queue_end": end, "sent": len(win.done) + len(win.live),
               "done": done, "tok_s": toks / args.seconds,
               "steps": len(samples)}
        rows.append(row)
        print(json.dumps(row), flush=True)
        for lv in win.live:                      # drain: drop the backlog
            eng.cancel(lv.req.rid)
        eng.run_until_idle()
        if grow:
            break
        knee = rate
    out = {"knee_req_s": knee, "workload": args.workload,
           "seed": args.seed, "seconds_per_rate": args.seconds,
           "device": devices[0].device_kind, "sweep": rows}
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(out, indent=1) + "\n")
    print(json.dumps({"knee_req_s": knee}))
    return 0 if knee is not None else 1


if __name__ == "__main__":
    sys.exit(main())
