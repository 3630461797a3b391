"""90th percentile over the window's admissions, prefix hits and misses
together, of a ``sched.admit`` span: prefix planning, page allocation,
the prefill, adoption into the arena and the first token on the host.
Attempts that found no pages (``n`` = 0) are left out (program spans:
``ctx.spans``, the engine's span records over the window; a run that
passes none reads nothing)."""

import numpy as np


def read(ctx):
    admits = [s.end_ns - s.start_ns for s in getattr(ctx, "spans", None) or ()
              if s.name == "sched.admit" and s.n > 0]
    if not admits:
        return None
    return float(np.percentile(admits, 90)) * 1e-6
