"""Beyond-paper benchmark: SS as the training-data coreset stage — batch
coverage utility and selection wall-time for uniform / SS / full-greedy
selection policies (the integration the LM stack actually uses)."""

from __future__ import annotations

import dataclasses
import time

from benchmarks.common import save
from repro.data import DataConfig, Pipeline, selection_quality


def run(seed: int = 0) -> dict:
    cfg = DataConfig(batch_size=16, seq_len=128, vocab_size=50304,
                     pool_factor=6, feature_dim=512)
    quality = selection_quality(cfg, steps=4, seed=seed)
    times = {}
    for sel in ("uniform", "ss", "greedy"):
        pipe = Pipeline(dataclasses.replace(cfg, selection=sel), seed=seed)
        pipe()  # warm-up / compile
        t0 = time.perf_counter()
        for _ in range(3):
            pipe()
        times[sel] = (time.perf_counter() - t0) / 3
    out = {"coverage_utility": quality, "batch_time_s": times,
           "ss_vs_uniform": quality["ss"] / quality["uniform"],
           "ss_vs_greedy": quality["ss"] / quality["greedy"]}
    print("data_selection:", out)
    save("data_selection", out)
    return out


if __name__ == "__main__":
    from repro.compile_cache import setup_compile_cache

    setup_compile_cache()
    run()
