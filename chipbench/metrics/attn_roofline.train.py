"""Least time of causal attention forward and backward (the
architecture's `train_attn_least_time`) over the device time of the
fp8_attention kernels."""
from bench import arch, trace


def read(ctx):
    w = ctx["work"]
    ker = trace.kernel_s(ctx["trace"], "fp8_attention")
    if w["kind"] != "train" or ker <= 0:
        return None
    least = w["steps"] * arch.by_name(w["arch"]).train_attn_least_time(
        w["z"], w["batch"], w["seq"], ctx["peaks"])
    return 100.0 * least / ker
