"""Share of the traced training window the device spent in ops whose
innermost program scope is `train.optimizer` (parameter cast, Adam
update and norms)."""
from bench import scopes


def read(ctx):
    return scopes.share(ctx, "train.optimizer")
