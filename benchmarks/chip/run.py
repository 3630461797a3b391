"""Run one cell of the on-chip serving benchmark once.

    python3 -m benchmarks.chip.run --workload danube-chat --seed 7 \\
        --seconds 30 --trace 0

from the root of a checkout.  The cell, its configuration, traffic mix
and metrics are looked up by name from ``BENCHMARK.json`` and the files
under ``benchmarks/chip/``.  The last line of standard output is one JSON
object; the numbers compared for ``correct`` are the last lines of
standard error too.  Without a TPU, or with fewer chips than the cell
asks for, or without the program's ``src/`` in the checkout, it exits
non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"


def enable_cache() -> None:
    """JAX's persistent cache at a fixed path inside the checkout, every
    program in it: only a checkout's first run of a cell compiles."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    # the TPU runtime's logs stay inside the checkout
    os.environ.setdefault("TPU_LOG_DIR", str(ROOT / ".tpu_logs"))
    if not (ROOT / "src" / "repro").is_dir():
        print("benchmark: no src/repro in this checkout: nothing to run",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    from benchmarks.chip import spec

    cell = spec.load(ROOT, args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"benchmark: JAX found no TPU (platform "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"benchmark: {args.workload} needs {cell.chips} chips, JAX "
              f"sees {len(devices)}", file=sys.stderr)
        return 2
    enable_cache()
    from benchmarks.chip import harness

    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         T_START, TRACE_DIR / args.workload)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
