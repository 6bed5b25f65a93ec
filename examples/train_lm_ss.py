"""End-to-end driver: train a ~small LM for a few hundred steps with the SS
coreset-selection data pipeline, checkpointing, and restart-on-preemption —
the (b) "end-to-end driver" deliverable, runnable on CPU.

    PYTHONPATH=src python examples/train_lm_ss.py [--steps 200] [--selection ss]

Compares the final loss of SS-selected batches against uniform selection on
the same redundant synthetic stream (the coreset pays off because duplicate
documents waste gradient steps).
"""

import argparse
import os
import shutil

import jax

from repro import configs
from repro.data import DataConfig, Pipeline
from repro.train import (
    Checkpointer,
    TrainConfig,
    make_train_state,
    make_train_step,
    resume_or_init,
    run,
)


def train(selection: str, steps: int, seed: int = 0, arch: str = "llama3.2-3b",
          ckpt_dir: str | None = None):
    cfg = configs.smoke(arch)
    tc = TrainConfig(optimizer="adamw", lr=1e-3, warmup_steps=10,
                     total_steps=steps)
    dc = DataConfig(batch_size=8, seq_len=96, vocab_size=cfg.vocab_size,
                    selection=selection, pool_factor=4, feature_dim=256,
                    dup_frac=0.5)
    pipe = Pipeline(dc, seed=seed)
    step = jax.jit(make_train_step(cfg, tc), donate_argnums=(0,))
    ckpt = Checkpointer(ckpt_dir or f"/tmp/repro_example_{selection}", keep=2)
    state_shape = jax.eval_shape(
        lambda: make_train_state(jax.random.PRNGKey(seed), cfg, tc))
    state, start, resumed = resume_or_init(
        ckpt, state_shape,
        lambda: make_train_state(jax.random.PRNGKey(seed), cfg, tc))
    if resumed:
        print(f"  resumed from step {start}")
    state, rep = run(state, step, pipe, ckpt, num_steps=steps,
                     start_step=start, ckpt_every=max(50, steps // 4),
                     log_every=max(1, steps // 8),
                     log_fn=lambda s: print("  " + s))

    # held-out eval: FRESH, duplicate-free documents.  (Train loss is the
    # wrong yardstick on a redundant stream — uniform batches contain
    # near-duplicates that are easy to memorize.)
    from repro.data.synthetic import lm_documents
    from repro.models import forward, lm_loss
    import jax.numpy as jnp

    docs = lm_documents(999_999, 32, dc.seq_len + 1, cfg.vocab_size,
                        dup_frac=0.0)
    toks, labels = jnp.asarray(docs[:, :-1]), jnp.asarray(docs[:, 1:])
    logits, _ = forward(cfg, state["params"], toks)
    eval_loss = float(lm_loss(cfg, logits, labels))
    return {"train": rep.metrics_history[-1]["loss"], "eval": eval_loss}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--selection", default="both",
                    choices=["ss", "uniform", "both"])
    args = ap.parse_args()

    results = {}
    sels = ["uniform", "ss"] if args.selection == "both" else [args.selection]
    for sel in sels:
        d = f"/tmp/repro_example_{sel}"
        shutil.rmtree(d, ignore_errors=True)
        print(f"[{sel}] training {args.steps} steps...")
        results[sel] = train(sel, args.steps, ckpt_dir=d)
    print("\nloss by selection policy (eval = held-out, duplicate-free):")
    for k, v in results.items():
        print(f"  {k:8s} train {v['train']:.4f}   eval {v['eval']:.4f}")
    return 0


if __name__ == "__main__":
    from repro.compile_cache import setup_compile_cache

    setup_compile_cache()
    raise SystemExit(main())
