"""Share of the traced training window the device spent in ops whose
innermost program scope is `fp8.sr_bits` (random-bit draws for
stochastic rounding)."""
from bench import scopes


def read(ctx):
    return scopes.share(ctx, "fp8.sr_bits")
