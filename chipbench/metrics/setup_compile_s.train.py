"""Seconds the program spent tracing, lowering and compiling before the
training window opened (its compile counters, `compile/trace_s + lower_s +
backend_s`)."""
from bench import scopes


def read(ctx):
    return scopes.setup_compile_s(ctx)
