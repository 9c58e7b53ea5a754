"""Median over the requests sent in the window of submit -> first token, on
the client side, in milliseconds.  The traced window holds some fifty
requests, too few for a tail; the 95th percentile over a full window swung
5-8% from run to run in this closed loop (PR 25)."""

import numpy as np


def read(ctx):
    ttft = [r.times[0] - r.t_submit for r in ctx.obs["sent"] if r.times]
    return 1e3 * float(np.median(ttft)) if len(ttft) >= 20 else None
