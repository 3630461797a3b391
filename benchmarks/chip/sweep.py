"""The sweep that finds a cell's knee, once, on the chip: one engine set
up as the cell's runs set it up and one window of open-loop traffic at
each rate given.

    python3 -m benchmarks.chip.sweep --workload danube-chat --seed 5 \\
        --seconds 30 --rates 2,3,4,5

Each rate gets an engine of its own, built and warmed as a run's is.
One JSON line per rate: TTFT and TPOT tails, requests due and finished,
and what was still waiting when the window closed.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time

from benchmarks.chip.run import ROOT


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--rates", required=True)
    args = p.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax

    from benchmarks.chip import harness, run, spec, traffic

    if jax.devices()[0].platform != "tpu":
        print("sweep: JAX found no TPU", file=sys.stderr)
        return 2
    run.enable_cache()
    cell = spec.load(ROOT, args.workload)
    vocab = cell.config["vocab_size"]
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        settings = dict(cell.settings, rate=rate)
        eng = harness.build(cell, args.seed)
        tr = traffic.generate(cell.mix, settings, args.seed + i, vocab,
                              args.seconds)
        harness.setup_traffic(eng, cell, args.seed, tr.docs)
        client = harness.Client(eng, tr.reqs)
        window_s, _ = harness.serve_window(client, args.seconds,
                                           time.perf_counter())
        e2e = harness.end_to_end(client, window_s)
        st = dict(eng.stats)
        ttft = [((r.times[0] if r.times else window_s) - r.req.due_s) * 1e3
                for r in client.recs.values()]
        print(json.dumps(dict(
            rate=rate, window_s=window_s,
            ttft_p50_ms=harness.percentile(ttft, 50),
            ttft_p90_ms=e2e["ttft_p90_ms"], tpot_p90_ms=e2e["tpot_p90_ms"],
            tokens_per_s=e2e["output_tokens_per_s"], due=e2e["attempted"],
            handed_over=len(client.recs),
            finished=sum(bool(r.reason) for r in client.recs.values()),
            waiting_at_close=len(eng.pending),
            active_at_close=len(eng.active_slots()),
            preempted=st["preempted"], peak_pages=st["peak_pages"],
            steps=st["steps"], admitted=st["admitted"],
            prefill_s=st["prefill_s"], decode_s=st["decode_s"])),
            flush=True)
        client.eng = None
        del eng
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
