"""Readings that set a cell's correctness limit, on the chip, in one
process: for each seed, a run of the cell as the benchmark makes it and,
on the same sample of served requests, the precision control in the
program's place, judged by the harness's own check: its gap and whether
it came out correct (it must not).

    python3 -m benchmarks.chip.calibrate --workload danube-chat \\
        --seconds 20 --seeds 11,12,13

The program's widest gap over the seeds is the lower reading; the
control's smallest is the upper one.  One JSON line per seed goes to
standard output, and a summary last.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from benchmarks.chip.run import ROOT, TRACE_DIR  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", required=True)
    args = p.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax

    from benchmarks.chip import harness, run, spec

    if jax.devices()[0].platform != "tpu":
        print("calibrate: JAX found no TPU", file=sys.stderr)
        return 2
    run.enable_cache()
    cell = spec.load(ROOT, args.workload)
    prog, ctl, verdicts = [], [], []
    t = T_START
    for seed in (int(x) for x in args.seeds.split(",")):
        res = harness.run(cell, seed, args.seconds, False, t,
                          TRACE_DIR / args.workload, control=True)
        t = time.perf_counter()
        c, k = res["checks"], res["control"]
        prog.append(c["max_logit_gap"]["value"])
        ctl.append(k["checks"]["max_logit_gap"]["value"])
        verdicts.append((res["correct"], k["correct"]))
        print(json.dumps(dict(seed=seed, program_gap=prog[-1],
                              program_correct=res["correct"],
                              control_gap=ctl[-1],
                              control_correct=k["correct"],
                              tokens=c["tokens_checked"]["value"],
                              engine=res["engine"],
                              metrics=res["metrics"])), flush=True)
    print(json.dumps(dict(workload=args.workload, lower=max(prog),
                          upper=min(g for g in ctl if g is not None),
                          program=prog, control=ctl,
                          verdicts=verdicts)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
