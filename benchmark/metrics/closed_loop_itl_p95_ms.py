"""95th percentile of every gap between consecutive tokens of every request
in the window, on the client side of ``stream()``, in milliseconds.  In this
closed loop at full occupancy a gap is one decode step plus the prefills
admitted between two steps, so the percentile sits on a staircase and swings
5% from run to run (PR 25): recorded here, judged in an open-loop cell."""

import numpy as np


def read(ctx):
    t0, t1 = ctx.obs["t0"], ctx.obs["t1"]
    gaps = [b - a for r in ctx.obs["records"]
            for a, b in zip(r.times, r.times[1:]) if t0 <= b < t1]
    return 1e3 * float(np.percentile(gaps, 95)) if len(gaps) >= 200 else None
