"""Training launcher: ``python -m repro.launch.train --arch <id> ...``.

Single-host entry; on a real pod slice the same file runs under
``jax.distributed.initialize()`` (multi-host) with the production mesh.
Supports reduced CPU runs (--reduced) and full-config runs on device grids.
"""

from __future__ import annotations

import argparse
import logging


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--shape", default="train_4k")
    p.add_argument("--reduced", action="store_true",
                   help="tiny same-family config (CPU-runnable)")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch", type=int, default=None)
    p.add_argument("--seq", type=int, default=None)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--microbatches", type=int, default=1)
    p.add_argument("--checkpoint-dir", default=None)
    p.add_argument("--checkpoint-every", type=int, default=50)
    p.add_argument("--softmax", default="two_pass",
                   choices=["two_pass", "three_pass_recompute",
                            "three_pass_reload"])
    p.add_argument("--mesh", default=None,
                   help="e.g. '4x2' => (data=4, model=2) on local devices")
    args = p.parse_args()

    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(levelname)s %(message)s")

    from repro.configs.base import SHAPES, ShapeCell
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_mesh
    from repro.models import build_model
    from repro.training.trainer import Trainer, TrainerConfig

    enable_compile_cache()
    mesh = None
    tp = 1
    if args.mesh:
        dims = tuple(int(x) for x in args.mesh.split("x"))
        axes = ("data", "model")[:len(dims)]
        mesh = make_mesh(dims, axes)
        tp = dict(zip(axes, dims)).get("model", 1)

    model = build_model(args.arch, tp=tp, reduced=args.reduced,
                        softmax_algorithm=args.softmax)
    base = SHAPES[args.shape]
    cell = ShapeCell(base.name,
                     args.seq or (64 if args.reduced else base.seq_len),
                     args.batch or (8 if args.reduced else
                                    base.global_batch),
                     "train")
    trainer = Trainer(model, cell, TrainerConfig(
        steps=args.steps, checkpoint_every=args.checkpoint_every,
        checkpoint_dir=args.checkpoint_dir, peak_lr=args.lr,
        microbatches=args.microbatches), mesh=mesh)
    trainer.run()
    last = trainer.metrics_history[-1] if trainer.metrics_history else {}
    print(f"final: {last}")


if __name__ == "__main__":
    main()
