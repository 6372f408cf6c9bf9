"""Share of the traced training window the device spent in ops whose
innermost program scope is `train.scaling` (delayed-scaling
observations, history update and scale churn)."""
from bench import scopes


def read(ctx):
    return scopes.share(ctx, "train.scaling")
