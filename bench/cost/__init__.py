"""Operations and bytes of the algorithm's own work, from shapes and counts.

The count is the algorithm's (arXiv:1606.00399, Algorithm 1 and greedy), not
any implementation's: the same whatever computes it, so that no kernel can
make its own yardstick stale.  Bytes are the compulsory traffic, each input
read once per call, so that no implementation can beat the count.
"""

import math

F32 = 4


def probe_count(n: int, r: int) -> int:
    """m = r log2 n probes per SS round (paper §3.2)."""
    return max(1, int(r * math.log2(max(n, 2))))


def pair_width(objective: str, n: int, n_features: int) -> int:
    """Terms one marginal gain sums over: features for coverage, the ground
    set's rows for facility location."""
    return n_features if objective == "coverage" else n


def least_time(ops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """max(ops / peak FLOP/s, bytes / peak bandwidth), and which bound."""
    t_ops = ops / float(peak["flops_per_s"])
    t_mem = nbytes / float(peak["hbm_bytes_per_s"])
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
