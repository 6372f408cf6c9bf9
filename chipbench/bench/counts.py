"""Operations and bytes that the algorithm needs, from shapes alone.

Everything counts what a step has to do, whatever implements it:
recomputation does not count, causal attention counts its lower triangle,
and the embedding gather counts no operations. GEMM operands count at one
byte (the FP8 formats of the recipe), GEMM outputs at two (bf16). These
are the pieces; each architecture (`models/<arch>.py`) counts its own
step with them.
"""
from __future__ import annotations


def gemm(m: int, k: int, n: int, *, a_bytes=1, b_bytes=1, out_bytes=2):
    """(operations, bytes) of an (m x k) @ (k x n) product."""
    return 2 * m * k * n, m * k * a_bytes + k * n * b_bytes + m * n * out_bytes


def least_time(ops: float, nbytes: float, peaks: dict) -> float:
    return max(ops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])


def causal_pairs(s: int) -> int:
    """(query, key) pairs of one causal sequence: its lower triangle."""
    return s * (s + 1) // 2

