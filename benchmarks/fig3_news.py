"""Paper Figures 3-5: per-day news summarization statistics — relative
utility, ROUGE-2 and F1 against topic-structured references, over many
synthetic "days" of varying size (the 3823-day NYT study, scaled to this
container)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import TopicNews, rouge2, rouge2_f1, save, timed
from repro.core import FeatureCoverage, greedy, sieve_streaming
from repro.core.sparsify import ss_sparsify

K = 10


def run(days=16, n_range=(800, 6000), seed=0) -> dict:
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    rows = []
    for d in range(days):
        n = int(rng.integers(*n_range))
        day = TopicNews(seed * 1000 + d, n)
        fn = FeatureCoverage(W=jnp.asarray(day.features()), phi="sqrt")

        res_g, t_g = timed(lambda: jax.block_until_ready(greedy(fn, K)))

        def run_ss():
            ss = ss_sparsify(fn, key, r=8, c=8.0)
            return jax.block_until_ready(greedy(fn, K, alive=ss.vprime)), ss

        (res_ss, ss), t_ss = timed(run_ss)
        res_sv, t_sv = timed(
            lambda: jax.block_until_ready(sieve_streaming(fn, K))
        )

        fg = float(res_g.value)
        sel = {
            "greedy": np.asarray(res_g.selected),
            "ss": np.asarray(res_ss.selected),
            "sieve": np.asarray([i for i in np.asarray(res_sv.selected) if i >= 0]),
        }
        row = {"day": d, "n": n, "vprime": int(jnp.sum(ss.vprime)),
               "t_greedy_s": t_g, "t_ss_s": t_ss, "t_sieve_s": t_sv}
        for name, idx in sel.items():
            docs = [day.docs[i] for i in idx]
            row[f"rouge2_{name}"] = rouge2(docs, day.reference)
            row[f"f1_{name}"] = rouge2_f1(docs, day.reference)
        row["rel_ss"] = float(res_ss.value) / fg
        row["rel_sieve"] = float(res_sv.value) / fg
        rows.append(row)
        print(f"fig3 day={d:2d} n={n:5d} rel_ss={row['rel_ss']:.4f} "
              f"rel_sieve={row['rel_sieve']:.4f} "
              f"rouge2 g/ss/sv={row['rouge2_greedy']:.3f}/"
              f"{row['rouge2_ss']:.3f}/{row['rouge2_sieve']:.3f}", flush=True)

    agg = {
        "days": days,
        "rel_ss_mean": float(np.mean([r["rel_ss"] for r in rows])),
        "rel_ss_p10": float(np.percentile([r["rel_ss"] for r in rows], 10)),
        "rel_sieve_mean": float(np.mean([r["rel_sieve"] for r in rows])),
        "rouge2": {m: float(np.mean([r[f"rouge2_{m}"] for r in rows]))
                   for m in ("greedy", "ss", "sieve")},
        "f1": {m: float(np.mean([r[f"f1_{m}"] for r in rows]))
               for m in ("greedy", "ss", "sieve")},
        "speedup_vs_greedy": float(
            np.mean([r["t_greedy_s"] / max(r["t_ss_s"], 1e-9) for r in rows])
        ),
    }
    save("fig3_news", {"rows": rows, "aggregate": agg})
    print("fig3 aggregate:", agg)
    return {"rows": rows, "aggregate": agg}


if __name__ == "__main__":
    from repro.compile_cache import setup_compile_cache

    setup_compile_cache()
    run()
