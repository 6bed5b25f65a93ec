"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-3b --smoke \\
        --steps 50 --batch 8 --seq 128 --selection ss

Wires every substrate together: config registry -> SS-selected data pipeline
-> sharded train step -> checkpointed, preemption-safe loop.  On this CPU
container use ``--smoke`` (reduced config); the same driver with the full
config and a TPU mesh is the production entry point.
"""

from __future__ import annotations

import argparse
import os

import jax

from repro import configs
from repro.data import DataConfig, Pipeline
from repro.launch.mesh import make_test_mesh
from repro.train import (
    Checkpointer,
    StragglerGuard,
    TrainConfig,
    abstract_train_state,
    make_train_state,
    resume_or_init,
    run,
    shard_train_step,
)

Array = jax.Array


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(configs.ARCHS))
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config (CPU)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--optimizer", default=None, choices=[None, "adamw", "adafactor"])
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--selection", default="ss",
                    choices=["none", "uniform", "greedy", "ss"])
    ap.add_argument("--pool-factor", type=int, default=4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--mesh", default="1x1",
                    help="dataxmodel, e.g. 2x2 (requires that many devices)")
    ap.add_argument("--straggler-deadline", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = configs.smoke(args.arch) if args.smoke else configs.get(args.arch)
    tc = TrainConfig(
        optimizer=args.optimizer
        or ("adafactor" if cfg.param_count() > 10e9 else "adamw"),
        lr=args.lr,
        warmup_steps=max(2, args.steps // 10),
        total_steps=args.steps,
        num_microbatches=args.microbatches,
    )
    dshape = tuple(int(x) for x in args.mesh.split("x"))
    mesh = make_test_mesh(dshape, ("data", "model"))

    dc = DataConfig(
        batch_size=args.batch,
        seq_len=args.seq,
        vocab_size=cfg.vocab_size,
        selection=args.selection,
        pool_factor=args.pool_factor,
        num_codebooks=cfg.num_codebooks,
        patch_count=cfg.num_patches if cfg.input_mode == "tokens+patches" else 0,
        d_model=cfg.d_model,
    )
    pipe = Pipeline(dc, seed=args.seed)

    state_shape = abstract_train_state(cfg, tc)
    with jax.set_mesh(mesh):
        step_fn, state_sh, batch_sharding = shard_train_step(
            mesh, cfg, tc, state_shape
        )
        ckpt = Checkpointer(os.path.join(args.ckpt_dir, cfg.name))
        state, start, resumed = resume_or_init(
            ckpt, state_shape,
            lambda: make_train_state(jax.random.PRNGKey(args.seed), cfg, tc),
            shardings=state_sh,
        )
        if resumed:
            print(f"resumed from step {start}")

        next_batch = pipe
        if args.straggler_deadline > 0:
            next_batch = StragglerGuard(
                pipe, lambda: None, deadline_s=args.straggler_deadline
            )
        state, report = run(
            state, step_fn, next_batch, ckpt,
            num_steps=args.steps, start_step=start,
            ckpt_every=args.ckpt_every, log_every=max(1, args.steps // 20),
        )
    print(
        f"done: {report.steps_done} steps"
        + (" (preempted)" if report.preempted else "")
        + (f", {report.straggler_skips} straggler skips"
           if report.straggler_skips else "")
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
