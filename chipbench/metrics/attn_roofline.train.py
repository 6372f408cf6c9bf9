"""Least time of causal attention forward and backward (bench.counts) over
the device time of the fp8_attention kernels."""
from bench import counts, trace


def read(ctx):
    w = ctx["work"]
    ker = trace.kernel_s(ctx["trace"], "fp8_attention")
    if w["kind"] != "train" or ker <= 0:
        return None
    least = w["steps"] * counts.train_attn_least_time(
        w["z"], w["batch"], w["seq"], ctx["peaks"])
    return 100.0 * least / ker
