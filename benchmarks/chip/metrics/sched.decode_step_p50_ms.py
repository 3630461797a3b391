"""Median over the window's decode bursts of the burst's time per step:
a ``sched.decode`` span (first dispatch to the tokens on the host) over
its ``runahead`` (program spans: ``ctx.spans``, the engine's span records
over the window; a run that passes none reads nothing)."""

import numpy as np


def read(ctx):
    steps = [(s.end_ns - s.start_ns) / s.runahead
             for s in getattr(ctx, "spans", None) or ()
             if s.name == "sched.decode"]
    if not steps:
        return None
    return float(np.median(steps)) * 1e-6
