"""Jit'd public wrapper for the stochastic_round kernel (padding + reshaping)."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.stochastic_round import kernel as _k
from repro.obs.trace import scope


@functools.partial(jax.jit,
                   static_argnames=("fmt", "saturate", "interpret",
                                    "use_onchip_prng"))
def stochastic_round_fp8(x, key, scale=None, *, fmt: str = "e5m2",
                         saturate: bool = True, interpret: bool = False,
                         use_onchip_prng: bool = False):
    """Quantize x -> fp8 (`fmt` in {'e5m2','e4m3'}) with stochastic rounding
    via the Pallas kernel.

    Accepts any rank; internally flattens to 2D (TPU tiles are 2D). `key` is
    a JAX PRNG key (operand-randomness path) or an int32 seed scalar
    (on-chip-PRNG path).
    """
    if scale is None:
        scale = jnp.ones((1,), jnp.float32)
    scale = jnp.asarray(scale, jnp.float32).reshape((1,))
    orig_shape = x.shape
    n = orig_shape[-1] if x.ndim >= 1 else 1
    x2 = x.reshape((-1, n))
    if use_onchip_prng:
        seed = jnp.asarray(key, jnp.int32).reshape((1,))
        out = _k.sr_quantize_kernel_onchip(x2, seed, scale, fmt=fmt,
                                           saturate=saturate)
    else:
        with scope("fp8.sr_bits"):
            rand8 = jax.random.bits(key, x2.shape, jnp.uint8)
        out = _k.sr_quantize_kernel(x2, rand8, scale, fmt=fmt,
                                    saturate=saturate, interpret=interpret)
    return out.reshape(orig_shape)


def stochastic_round_e5m2(x, key, scale=None, *, saturate: bool = True,
                          interpret: bool = False,
                          use_onchip_prng: bool = False):
    """Back-compat alias for the e5m2-hardwired name."""
    return stochastic_round_fp8(x, key, scale, fmt="e5m2", saturate=saturate,
                                interpret=interpret,
                                use_onchip_prng=use_onchip_prng)
