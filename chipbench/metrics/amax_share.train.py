"""Share of the traced training window the device spent in ops whose
innermost program scope is `fp8.amax` (amax and health reductions
outside the kernels)."""
from bench import scopes


def read(ctx):
    return scopes.share(ctx, "fp8.amax")
