"""The main path's Pallas kernels compile for a TPU v5e.

Each case compiles one kernel at qwen2-1.5b widths (d_model 1536, d_ff 8960,
12 query / 2 kv heads of 128, 4096 tokens) for a described v5e chip — no
chip is attached, the TPU compiler runs here — and checks that the compiled
program holds the Mosaic kernel (`tpu_custom_call`). What the compiler
refuses here (block shapes off the (8, 128) tiling, casts Mosaic cannot
lower) would otherwise first show on the chip.

The topology is described inside a module fixture, never at import: only one
process at a time may load the TPU library, and every pytest-xdist worker
imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fp8_attention.ops import fp8_attention_bwd, fp8_attention_fwd
from repro.kernels.fused_quant_matmul.ops import fused_quant_matmul

M, K, N = 4096, 1536, 8960          # tokens, d_model, d_ff
H, HKV, D, S = 12, 2, 128, 4096     # heads, kv heads, head_dim, context
F8 = {"e5m2": jnp.float8_e5m2, "e4m3": jnp.float8_e4m3fn}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip cannot be read back from the
    # persistent cache; keep it out of the cache.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled, name):
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert name in text, f"kernel {name} missing from the compiled program"


GEMM_SHAPES = {"nn": ((M, K), (K, N)),     # forward  Y = A.W
               "nt": ((M, N), (K, N)),     # dgrad   dA = dY.W^T
               "tn": ((M, K), (M, N))}     # wgrad   dW = A^T.dY


@pytest.mark.parametrize("rounding", ["sr", "rne"])
@pytest.mark.parametrize("fmt", ["e5m2", "e4m3"])
@pytest.mark.parametrize("dims", ["nn", "nt", "tn"])
def test_fused_gemm_compiles(one_chip, dims, fmt, rounding):
    """Fused quantize-epilogue GEMM with its amax and health-count
    outputs, every layout, both formats, both roundings."""
    a, b = GEMM_SHAPES[dims]
    compiled = fused_quant_matmul.lower(
        _sds(one_chip, a, F8[fmt]), _sds(one_chip, b, F8[fmt]),
        _sds(one_chip, (2,), jnp.uint32), _sds(one_chip, (1,), jnp.float32),
        dims=dims, out_format=fmt, rounding=rounding, with_amax=True,
        with_counts=True).compile()
    _assert_kernel(compiled, f"fused_quant_matmul_{dims}")


def _qkv(one_chip, batch, q_len):
    f8 = F8["e4m3"]
    return (_sds(one_chip, (batch, H, q_len, D), f8),
            _sds(one_chip, (batch, HKV, S, D), f8),
            _sds(one_chip, (batch, HKV, S, D), f8))


ATTN_KW = dict(fmt_s="e4m3", fmt_p="e4m3")


def test_attention_fwd_compiles(one_chip):
    """Training forward (causal), with the S/P health counts."""
    q, k, v = _qkv(one_chip, 1, S)
    compiled = fp8_attention_fwd.lower(
        q, k, v, _sds(one_chip, (), jnp.uint32),
        _sds(one_chip, (4,), jnp.float32), mask_mode="causal",
        with_counts=True, **ATTN_KW).compile()
    _assert_kernel(compiled, "fp8_attention_fwd")


@pytest.fixture(scope="module")
def attention_bwd(one_chip):
    """One compile of the backward: the dQ and the dK/dV kernels."""
    q, k, v = _qkv(one_chip, 1, S)
    return fp8_attention_bwd.lower(
        q, k, v, _sds(one_chip, (1, H, S, D), F8["e5m2"]),
        _sds(one_chip, (), jnp.uint32), _sds(one_chip, (10,), jnp.float32),
        mask_mode="causal", with_counts=True, **ATTN_KW).compile()


@pytest.mark.parametrize("kernel", ["dq", "dkv"])
def test_attention_bwd_compiles(attention_bwd, kernel):
    _assert_kernel(attention_bwd, f"fp8_attention_bwd_{kernel}")


def test_attention_decode_compiles(one_chip):
    """Serving decode: one query token per row against a masked cache."""
    q, k, v = _qkv(one_chip, 8, 1)
    compiled = fp8_attention_fwd.lower(
        q, k, v, _sds(one_chip, (), jnp.uint32),
        _sds(one_chip, (4,), jnp.float32), mask_mode="kv",
        kv_mask=_sds(one_chip, (8, S), jnp.int32), **ATTN_KW).compile()
    _assert_kernel(compiled, "fp8_attention_fwd")


def test_attention_paged_chunk_compiles(one_chip):
    """Paged serving step: 128-token prefill chunks against gathered
    slots, per-row chunk coordinates."""
    q, k, v = _qkv(one_chip, 8, 128)
    compiled = fp8_attention_fwd.lower(
        q, k, v, _sds(one_chip, (), jnp.uint32),
        _sds(one_chip, (4,), jnp.float32), mask_mode="chunk",
        kv_mask=_sds(one_chip, (8, S), jnp.int32),
        chunk_pos=_sds(one_chip, (8, 2), jnp.int32), **ATTN_KW).compile()
    _assert_kernel(compiled, "fp8_attention_fwd")
