"""Operations and bytes that the algorithm needs, from shapes alone.

Everything here counts what a step has to do, whatever implements it:
recomputation does not count, causal attention counts its lower triangle,
and the embedding gather counts no operations. GEMM operands count at one
byte (the FP8 formats of the recipe), GEMM outputs at two (bf16).
"""
from __future__ import annotations


def projections(z: dict):
    """(K, N) of the seven projections of one decoder layer."""
    d, q, k, f = z["d"], z["h"] * z["hd"], z["hkv"] * z["hd"], z["f"]
    return [(d, q), (d, k), (d, k), (q, d), (d, f), (d, f), (f, d)]


def layer_matmul_params(z: dict) -> int:
    return sum(k * n for k, n in projections(z))


def gemm(m: int, k: int, n: int, *, a_bytes=1, b_bytes=1, out_bytes=2):
    """(operations, bytes) of an (m x k) @ (k x n) product."""
    return 2 * m * k * n, m * k * a_bytes + k * n * b_bytes + m * n * out_bytes


def least_time(ops: float, nbytes: float, peaks: dict) -> float:
    return max(ops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])


def causal_pairs(s: int) -> int:
    """(query, key) pairs of one causal sequence: its lower triangle."""
    return s * (s + 1) // 2


# -- training ----------------------------------------------------------------

def train_flops_per_token(z: dict, seq: int) -> float:
    """Model operations per trained token, forward and backward (3x the
    forward), without recomputation. Causal attention counts half."""
    dense = z["L"] * layer_matmul_params(z) + z["d"] * z["V"]
    attn = z["L"] * 4 * z["h"] * z["hd"] * causal_pairs(seq) / seq
    return 3 * (2 * dense + attn)


def train_gemm_least_time(z: dict, tokens: int, peaks: dict) -> float:
    """Least time of the fused FP8 GEMMs of one training step: each
    projection of each layer forward (M x K x N), its input gradient
    (M x N x K) and its weight gradient (K x M x N)."""
    t = 0.0
    for k, n in projections(z):
        for m_, k_, n_ in ((tokens, k, n), (tokens, n, k), (k, tokens, n)):
            t += least_time(*gemm(m_, k_, n_), peaks)
    return z["L"] * t


def train_attn_least_time(z: dict, batch: int, seq: int,
                          peaks: dict) -> float:
    """Least time of causal attention, forward plus backward (twice the
    forward's operations), over all layers."""
    h, hkv, hd = z["h"], z["hkv"], z["hd"]
    fwd_ops = 4 * batch * h * hd * causal_pairs(seq)
    qkv = batch * seq * hd * (h + 2 * hkv)
    o = batch * seq * h * hd
    fwd_bytes = qkv + 2 * o
    bwd_bytes = qkv + 2 * 2 * o + 2 * qkv
    return z["L"] * (least_time(fwd_ops, fwd_bytes, peaks)
                     + least_time(2 * fwd_ops, bwd_bytes, peaks))
