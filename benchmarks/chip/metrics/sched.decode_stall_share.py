"""Share of the window in which decoding slots waited on admissions: the
time in ``sched.admit`` spans that prefilled something (``n`` > 0) and
began while a slot was decoding (``stalled``), over the window (program
spans: ``ctx.spans``, the engine's span records over the window; a run
that passes none reads nothing)."""


def read(ctx):
    admits = [s for s in getattr(ctx, "spans", None) or ()
              if s.name == "sched.admit" and s.n > 0]
    if not admits:
        return None
    stall_ns = sum(s.end_ns - s.start_ns for s in admits if s.stalled)
    return 100.0 * stall_ns * 1e-9 / ctx.window_s
