"""Serving with SS KV-cache pruning (beyond-paper): prefill a prompt, prune
the KV cache to a budget with submodular selection of representative
positions, keep decoding, and compare fidelity against random pruning.

The selection runs through the summarization *service*
(repro.serve.summarize_service): the decode batch's pooled key-features are
one micro-batched lane of SS + compact greedy, executed as a single compiled
loop — ``Engine.prune_kv`` rides the same execution core, so the explicit
service round-trip below (through the stable ``repro.api`` facade) selects
the identical positions.

    PYTHONPATH=src python examples/serve_kv_pruning.py
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro import api, configs
from repro.models import init_params
from repro.serve import Engine, KVSelectConfig, ServeConfig, SummarizeRequest
from repro.serve.kv_select import pooled_keys


def main() -> int:
    cfg = configs.smoke("qwen2-7b")
    key = jax.random.PRNGKey(0)
    params = init_params(key, cfg)
    B, S, budget = 2, 48, 16
    toks = jax.random.randint(key, (B, S), 0, cfg.vocab_size)

    engine = Engine(cfg, params, ServeConfig(max_len=S + 16))
    logits, cache = engine.prefill(toks)
    nxt = jnp.argmax(logits, -1).astype(jnp.int32)
    ref, _ = engine.decode_with_cache(nxt, cache, jnp.int32(S))

    # SS pruning — Engine.prune_kv drives the service's batched execution
    # core; KV selection knobs ride KVSelectConfig, execution knobs its
    # nested RunConfig.
    pruned, clen, kept = engine.prune_kv(
        cache, S, key, KVSelectConfig(budget=budget)
    )
    out_ss, _ = engine.decode_with_cache(nxt, pruned, clen, pos=jnp.int32(S))

    # The same selection as an explicit service round-trip: one request per
    # decode row, same per-row keys — the queue micro-batches them into one
    # lane and must pick the identical positions.
    svc = api.serve(api.RunConfig(backend="oracle", max_batch=8))
    feats = pooled_keys(cache, S)
    row_keys = jax.random.split(key, B)
    responses = svc.run([
        SummarizeRequest(k=budget, key=row_keys[i], features=feats[i])
        for i in range(B)
    ])
    kept_svc = jnp.stack([jnp.sort(r.selected) for r in responses])
    assert bool(jnp.all(kept_svc == kept)), "service/prune_cache must agree"
    st = svc.stats()
    print(f"service round-trip: {st['queries']} queries in {st['batches']} "
          f"micro-batch(es), padding waste {st['padding_waste_frac']:.0%}, "
          f"|V'|={responses[0].vprime_size}, "
          f"eps^={responses[0].eps_hat:.4f}")

    # random pruning baseline
    rng = np.random.default_rng(0)
    kept_r = jnp.asarray(
        np.sort(rng.choice(S, budget, replace=False))
    )[None].repeat(B, 0)

    def compact(path, leaf):
        names = [p.key for p in path if hasattr(p, "key")]
        if names[-1] not in ("k", "v"):
            return leaf
        def per_row(row, idx):
            return jnp.zeros_like(row).at[:budget].set(row[idx])
        if leaf.ndim == 5:
            return jax.vmap(lambda g: jax.vmap(per_row)(g, kept_r))(leaf)
        return jax.vmap(per_row)(leaf, kept_r)

    rand = jax.tree_util.tree_map_with_path(compact, cache)
    out_r, _ = engine.decode_with_cache(nxt, rand, jnp.int32(budget),
                                        pos=jnp.int32(S))

    mse_ss = float(jnp.mean((out_ss - ref) ** 2))
    mse_r = float(jnp.mean((out_r - ref) ** 2))
    agree_ss = float(jnp.mean(jnp.argmax(out_ss, -1) == jnp.argmax(ref, -1)))
    agree_r = float(jnp.mean(jnp.argmax(out_r, -1) == jnp.argmax(ref, -1)))
    print(f"KV cache {S} -> {budget} positions "
          f"({100 * budget / S:.0f}% kept)")
    print(f"  SS pruning:     logit MSE {mse_ss:.4f}, "
          f"next-token agreement {agree_ss:.2f}")
    print(f"  random pruning: logit MSE {mse_r:.4f}, "
          f"next-token agreement {agree_r:.2f}")
    print("kept positions (row 0):", kept[0].tolist())
    return 0


if __name__ == "__main__":
    from repro.compile_cache import setup_compile_cache

    setup_compile_cache()
    raise SystemExit(main())
