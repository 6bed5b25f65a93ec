"""Video summarization with SS (paper §4.3 / §5.13) on a synthetic SumMe-like
video: select 15% of frames, compare SS against full greedy and the first-15%
baseline, report timing and F1 against the novelty reference.

    PYTHONPATH=src python examples/video_summarize.py [--frames 2000]
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import frame_f1
from benchmarks.table2_video import _reference
from repro.core import FeatureCoverage, greedy
from repro.core.sparsify import ss_sparsify
from repro.data import video


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--frames", type=int, default=2000)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    X = video(args.seed, args.frames, n_features=256)
    k = int(0.15 * args.frames)
    fn = FeatureCoverage(W=jnp.asarray(X), phi="sqrt")
    print(f"video: {args.frames} frames, budget k = {k} (15%)")

    t0 = time.perf_counter()
    full = jax.block_until_ready(greedy(fn, k))
    t_full = time.perf_counter() - t0

    key = jax.random.PRNGKey(args.seed)
    t0 = time.perf_counter()
    ss = ss_sparsify(fn, key, r=8, c=8.0)
    red = jax.block_until_ready(greedy(fn, k, alive=ss.vprime))
    t_ss = time.perf_counter() - t0

    ref = _reference(X)
    nv = int(jnp.sum(ss.vprime))
    print(f"greedy: f={float(full.value):.3f}  {t_full:.2f}s")
    print(f"SS:     f={float(red.value):.3f}  {t_ss:.2f}s  "
          f"|V'|={nv} ({100 * nv / args.frames:.0f}% kept)")
    print(f"relative utility: {float(red.value / full.value):.4f}")
    for name, sel in [("greedy", np.asarray(full.selected)),
                      ("ss", np.asarray(red.selected)),
                      ("first15%", np.arange(k))]:
        print(f"  F1 vs reference [{name:9s}]: "
              f"{frame_f1(sel, ref, args.frames):.3f}")
    return 0


if __name__ == "__main__":
    from repro.compile_cache import setup_compile_cache

    setup_compile_cache()
    raise SystemExit(main())
