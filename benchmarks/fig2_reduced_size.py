"""Paper Figure 2: relative utility f(S)/f(S_greedy) and SS time vs the size
of the reduced set |V'| (drive by sweeping r in [2, 20] step 2)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import save, timed
from repro.core import FeatureCoverage, greedy
from repro.core.sparsify import ss_sparsify
from repro.data import news_day

K = 10


def run(n=4096, n_features=512, seed=0, rs=tuple(range(2, 21, 2))) -> dict:
    W = jnp.asarray(news_day(seed, n, n_features))
    fn = FeatureCoverage(W=W, phi="sqrt")
    ref = greedy(fn, K)
    fg = float(ref.value)
    key = jax.random.PRNGKey(seed)
    rows = []
    for r in rs:
        def run_ss():
            ss = ss_sparsify(fn, key, r=r, c=8.0)
            res = greedy(fn, K, alive=ss.vprime)
            return jax.block_until_ready((res, ss))

        (res, ss), t = timed(run_ss)
        rows.append({
            "r": int(r),
            "vprime": int(jnp.sum(ss.vprime)),
            "rel_utility": float(res.value) / fg,
            "eps_hat": float(ss.eps_hat),
            "t_ss_s": t,
        })
        print(f"fig2 r={r:2d} |V'|={rows[-1]['vprime']:5d} "
              f"rel={rows[-1]['rel_utility']:.4f} t={t:.2f}s", flush=True)
    save("fig2_reduced_size", rows)
    return {"rows": rows, "f_greedy": fg}


if __name__ == "__main__":
    from repro.compile_cache import setup_compile_cache

    setup_compile_cache()
    run()
