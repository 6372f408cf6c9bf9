"""Model FLOP/s utilization of training: the operations the step needs
per token (the architecture's `train_flops_per_token`) times the tokens
of the traced steps, over the traced window, chips and the bf16 peak."""
from bench import arch


def read(ctx):
    w = ctx["work"]
    if w["kind"] != "train" or ctx["trace"]["window_s"] <= 0:
        return None
    ops = arch.by_name(w["arch"]).train_flops_per_token(w["z"], w["seq"]) \
        * w["tokens"]
    return 100.0 * ops / (ctx["trace"]["window_s"] * ctx["chips"]
                          * ctx["peaks"]["bf16_flops"])
