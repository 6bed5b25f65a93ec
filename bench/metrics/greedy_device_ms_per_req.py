"""Greedy: device time of the modules the layer table maps to batched
greedy, over the queries whose chunks started in the profiled window."""

from bench.metrics.layer_time import per_request_ms


def read(ctx):
    return per_request_ms(ctx, "greedy")
