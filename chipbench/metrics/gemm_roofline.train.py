"""Least time of the training step's FP8 GEMMs (the architecture's
`train_gemm_least_time`: bf16 peak and HBM bandwidth) over the device time
of the fused_quant_matmul kernels."""
from bench import arch, trace


def read(ctx):
    w = ctx["work"]
    ker = trace.kernel_s(ctx["trace"], "fused_quant_matmul")
    if w["kind"] != "train" or ker <= 0:
        return None
    least = w["steps"] * arch.by_name(w["arch"]).train_gemm_least_time(
        w["z"], w["batch"] * w["seq"], ctx["peaks"])
    return 100.0 * least / ker
