"""Benchmark utilities: timing, CSV output, size grids.

Timing protocol mirrors the paper's (SS6.2): warm up, run repeatedly for a
minimum wall time, report the median over repetitions.  On this container the
implementations under test are the XLA-compiled jnp forms (the Pallas kernels
target TPU; interpret mode is not a performance artifact), so the CPU numbers
play the role of the paper's AVX numbers: same algorithms, same pass
structure, different vector ISA.
"""

from __future__ import annotations

import time

import jax
import numpy as np


# Timing defaults; ``benchmarks.run --smoke`` drops them to a few quick
# reps so every benchmark module stays executable in CI without burning
# minutes.
REPS = 7
MIN_TIME_S = 0.2
_SMOKE = False


def smoke_mode() -> None:
    """Switch the module-wide timing protocol to median-of-3 over minimal
    wall time.  Overrides benchmarks' explicit per-call reps/min_time_s
    too — smoke is a rot check, not a measurement, but its numbers also
    feed the CI regression gate (scripts/check_bench.py), and a single
    rep flaps past the gate's 30% threshold even on an idle machine."""
    global REPS, MIN_TIME_S, _SMOKE
    REPS, MIN_TIME_S, _SMOKE = 3, 0.15, True


def time_fn(fn, *args, min_time_s: float | None = None,
            reps: int | None = None) -> float:
    """Median seconds/call over ``reps`` measurements (paper protocol)."""
    if _SMOKE or min_time_s is None:
        min_time_s = MIN_TIME_S
    if _SMOKE or reps is None:
        reps = REPS
    fn(*args)                                     # compile + warm
    jax.block_until_ready(fn(*args))
    medians = []
    for _ in range(reps):
        t0 = time.perf_counter()
        calls = 0
        while time.perf_counter() - t0 < min_time_s / reps:
            jax.block_until_ready(fn(*args))
            calls += 1
        medians.append((time.perf_counter() - t0) / max(calls, 1))
    return float(np.median(medians))


def emit(rows: list[tuple], header=("name", "us_per_call", "derived")):
    print(",".join(header))
    for r in rows:
        print(",".join(str(x) for x in r))
    return rows


def json_payload(benchmarks: dict, mode: str) -> dict:
    """The check_bench.py metrics schema, shared by every ``--json``
    emitter.  ``devices`` lets the gate skip sharded-lane rows when the
    runner has a single device (no sharded lane could have run)."""
    return dict(schema=1, mode=mode, backend=jax.default_backend(),
                devices=jax.device_count(), benchmarks=benchmarks)


# Array sizes (f32 elements): spanning L1/L2/L3/DRAM like the paper's sweep.
SIZES = [2 ** k for k in range(10, 24, 2)]        # 1K .. 8M elements
OUT_OF_CACHE = 8 * 2 ** 20                        # 8M f32 = 32 MB
