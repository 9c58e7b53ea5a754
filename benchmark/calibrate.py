"""The readings the limits of ``correct`` are set from, on the chip, many
seeds in one process (set-up is most of a run):

    python3 -m benchmark.calibrate --workload NAME --seeds 12 --controls 3 \\
        --first-seed 4000000007 --seconds 4 --out chiprun_out/calibrate.jsonl

For every seed the cell's program runs its set-up and a short window at the
cell's own load, releases its state, and ``jobs/<job>.py calibrate`` reads
the numbers compared; for the first ``--controls`` seeds it also reads the
control (the reference at the precision below the configuration's) and the
faults planted in the reference.  One JSON line a seed; ``PERF.md`` holds
the readings the limits were set from.  The benchmark's own runs never run
this.  ``--rehearsal`` as in ``benchmark/run.py``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from benchmark import run as harness


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="benchmark.calibrate")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=4000000007)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    out = open(args.out, "a") if args.out else None
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        run_args = argparse.Namespace(
            workload=args.workload, seed=seed, seconds=args.seconds, trace=0,
            rehearsal=args.rehearsal, describe=None)
        _, cell, job, ctx = harness.make_context(run_args)
        t0 = time.perf_counter()
        try:
            job.setup(ctx)
            job.window(ctx, args.seconds)
        finally:
            job.release(ctx)
        readings = job.calibrate(ctx, with_control=i < args.controls)
        line = {"workload": cell["name"], "seed": seed, "readings": readings,
                "attempted": ctx.obs["attempted"], "failed": ctx.obs["failed"],
                "seconds": time.perf_counter() - t0,
                "rehearsal": args.rehearsal}
        text = json.dumps(line)
        print(text, flush=True)
        if out:
            out.write(text + "\n")
            out.flush()
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
