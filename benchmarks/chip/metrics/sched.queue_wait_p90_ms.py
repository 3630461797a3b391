"""90th percentile over the window's admissions of the request's wait in
the engine's queue: a ``sched.queue`` span, from ``submit()`` (or a
preemption's requeue) to the start of the admission that took it
(program spans: ``ctx.spans``, the engine's span records over the
window; a run that passes none reads nothing)."""

import numpy as np


def read(ctx):
    waits = [s.end_ns - s.start_ns for s in getattr(ctx, "spans", None) or ()
             if s.name == "sched.queue"]
    if not waits:
        return None
    return float(np.percentile(waits, 90)) * 1e-6
