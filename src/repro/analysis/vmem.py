"""Analytic per-kernel VMEM footprint model for the fused Pallas kernels.

Byte accounting per grid step, straight from the kernels' BlockSpecs and
scratch_shapes (`kernels/fused_quant_matmul/kernel.py`,
`kernels/fp8_attention/kernel.py`):

 * every grid-blocked input/output block is counted TWICE — Mosaic's grid
   pipeline revolves two buffers per blocked ref so the next grid step's
   DMA overlaps compute;
 * scratch (`pltpu.VMEM`) refs are single-buffered (persistent across the
   innermost grid dim);
 * the attention kernels materialize per-(q-tile, kv-stripe) score/P
   tiles in vector registers / VMEM; the model charges one f32 + one fp8
   (bq, bkv) tile forward and two of each backward (dP and dS chains);
 * SMEM operands (scales, seeds) are charged at their true byte size;
   each kernel's observation output (amaxes, health counts) is one
   (8, 128) f32 stats block per grid cell (STATS_TILE_BYTES);
 * head_dim is padded to LANE (128) exactly as the ops-layer padding
   contract does before the kernel sees it.

The budget defaults to a full 16 MiB/core of TPU VMEM.  The model is
deliberately a lower bound on what Mosaic will actually allocate (it
ignores compiler spills and semaphore overhead), so a config the model
rejects can NEVER fit — safe for pruning autotune candidates and
refusing explicit knobs — while a config it accepts may still be tight.

Consumers: `kernels/autotune.py` (prune can't-fit sweep candidates before
timing them), `launch/specs.py` (reject oversized explicit
attn_block_q/attn_block_kv at spec-build time), and
`analysis/precision_lint.py` (the vmem_fit pass over built cells).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

from repro.kernels.autotune import LANE, TQ

VMEM_BYTES = 16 * 1024 * 1024   # per-core VMEM budget the model fits into
DMA_BUF = 2                     # grid-pipeline double buffering factor
STATS_TILE_BYTES = 8 * 128 * 4  # one (8, 128) f32 observation block


def _budget(budget: Optional[int]) -> int:
    return VMEM_BYTES if budget is None else int(budget)


def _pad_lane(d: int) -> int:
    return -(-max(int(d), 1) // LANE) * LANE


@dataclasses.dataclass(frozen=True)
class VmemEstimate:
    """Modeled per-grid-step VMEM footprint of one kernel launch."""
    kernel: str
    blocks: Dict[str, int]
    parts: Dict[str, int]
    budget_bytes: int = VMEM_BYTES

    @property
    def total_bytes(self) -> int:
        return int(sum(self.parts.values()))

    @property
    def fits(self) -> bool:
        return self.total_bytes <= self.budget_bytes

    def describe(self) -> str:
        blocks = ", ".join(f"{k}={v}" for k, v in self.blocks.items())
        return (f"{self.kernel}[{blocks}]: modeled VMEM "
                f"{self.total_bytes} bytes "
                f"({self.total_bytes / 2**20:.2f} MiB) vs budget "
                f"{self.budget_bytes} bytes "
                f"({self.budget_bytes / 2**20:.2f} MiB)")

    def to_dict(self) -> dict:
        return {"kernel": self.kernel, "blocks": dict(self.blocks),
                "vmem_bytes": self.total_bytes,
                "budget_bytes": self.budget_bytes, "fits": self.fits}


# -------------------------------------------------------------- fused GEMM
def gemm_operand_bytes(m: int, n: int, bm: int, bn: int) -> Tuple[int, int]:
    """(a_bytes, b_bytes): element width of the fused GEMM's operand blocks
    for a logical (m, n) output at (bm, bn) tiles — 2 for an operand the ops
    layer widens to bf16 (`kernel.widened_operands`), 1 for fp8."""
    from repro.kernels.fused_quant_matmul.kernel import widened_operands
    wa, wb = widened_operands(m, n, bm, bn)
    return (2 if wa else 1), (2 if wb else 1)


def gemm_vmem(bm: int, bk: int, bn: int, *, dims: str = "nn",
              with_amax: bool = True, a_bytes: int = 1, b_bytes: int = 1,
              budget: Optional[int] = None) -> VmemEstimate:
    """Fused quantize-epilogue GEMM (and the plain fp8_matmul, whose
    working set is a strict subset): a/b blocks at their own element
    width (`a_bytes`/`b_bytes`: 1 for fp8, 2 for bf16; see
    `gemm_operand_bytes`) + u8 SR-bits block in, fp8 out block + the
    amax/health stats block out, one (bm, bn) f32 accumulator scratch.
    Layout transposes (nn/nt/tn) permute block dims, not bytes."""
    a_blk = bm * bk * a_bytes
    b_blk = bk * bn * b_bytes
    rand_blk = bm * bn                    # uint8 SR bits
    out_blk = bm * bn                     # fp8 payload
    tiles = STATS_TILE_BYTES if with_amax else 0   # counts share it
    parts = {
        "in_blocks_x2": DMA_BUF * (a_blk + b_blk + rand_blk),
        "out_blocks_x2": DMA_BUF * (out_blk + tiles),
        "acc_scratch_f32": bm * bn * 4,
    }
    return VmemEstimate("fused_gemm", {"bm": bm, "bk": bk, "bn": bn,
                                       "a_bytes": a_bytes,
                                       "b_bytes": b_bytes},
                        parts, _budget(budget))


# --------------------------------------------------------------- attention
def attn_fwd_vmem(block_q: int, block_kv: int, head_dim: int, *,
                  mask_mode: str = "causal",
                  budget: Optional[int] = None) -> VmemEstimate:
    """One-pass fwd kernel, grid (B, H, nq, nk): fp8 q/k/v blocks in
    (+ per-stripe int32 mask block for kv/chunk modes), bf16 o block +
    the stats block out, (bq, 1) m/l + (bq, dp) f32 accumulator scratch, and
    the transient (bq, bkv) score (f32) + P (fp8) tiles."""
    bq, bkv, dp = int(block_q), int(block_kv), _pad_lane(head_dim)
    mask_blk = 0
    if mask_mode in ("kv", "chunk"):
        mask_blk = bkv * 4                 # int32 validity / slot positions
    out_tiles = STATS_TILE_BYTES           # amaxes and counts share it
    parts = {
        "in_blocks_x2": DMA_BUF * (bq * dp + 2 * bkv * dp + mask_blk),
        "out_blocks_x2": DMA_BUF * (bq * dp * 2 + out_tiles),
        "scratch_f32": (2 * bq + bq * dp) * 4,
        "score_tiles": bq * bkv * (4 + 1),
    }
    return VmemEstimate(
        "fp8_attention_fwd",
        {"block_q": bq, "block_kv": bkv, "head_dim_padded": dp},
        parts, _budget(budget))


def attn_bwd_dq_vmem(block_q: int, block_kv: int, head_dim: int, *,
                     budget: Optional[int] = None) -> VmemEstimate:
    """dQ kernel, grid (B, H, nq, 4*nk): fp8 q/k/v/do blocks in, f32 dq
    block + (bq, 1) m/l/rd statistics + the stats block out, 3x (bq, 1) +
    (bq, dp) f32 scratch, transient score/P and dP/dS tiles."""
    bq, bkv, dp = int(block_q), int(block_kv), _pad_lane(head_dim)
    out_tiles = STATS_TILE_BYTES
    parts = {
        "in_blocks_x2": DMA_BUF * (2 * bq * dp + 2 * bkv * dp),
        "out_blocks_x2": DMA_BUF * (bq * dp * 4 + 3 * bq * 4 + out_tiles),
        "scratch_f32": (3 * bq + bq * dp) * 4,
        "score_tiles": bq * bkv * (2 * 4 + 2 * 1),
    }
    return VmemEstimate(
        "fp8_attention_bwd_dq",
        {"block_q": bq, "block_kv": bkv, "head_dim_padded": dp},
        parts, _budget(budget))


def attn_bwd_dkv_vmem(block_q: int, block_kv: int, head_dim: int, *,
                      budget: Optional[int] = None) -> VmemEstimate:
    """dK/dV kernel, grid (B, Hkv, nk, group*nq): fp8 q/do blocks +
    (bq, 1) m/l/rd statistics + fp8 k/v blocks in, two f32 (bkv, dp)
    accumulating out blocks, transient score/dS tiles."""
    bq, bkv, dp = int(block_q), int(block_kv), _pad_lane(head_dim)
    parts = {
        "in_blocks_x2": DMA_BUF * (2 * bq * dp + 2 * bkv * dp
                                   + 3 * bq * 4),
        "out_blocks_x2": DMA_BUF * (2 * bkv * dp * 4),
        "score_tiles": bq * bkv * (2 * 4 + 2 * 1),
    }
    return VmemEstimate(
        "fp8_attention_bwd_dkv",
        {"block_q": bq, "block_kv": bkv, "head_dim_padded": dp},
        parts, _budget(budget))


def attn_vmem(kind: str, block_q: int, block_kv: int, head_dim: int, *,
              mask_mode: str = "causal",
              budget: Optional[int] = None) -> VmemEstimate:
    """Worst-case estimate for an attention pass: the fwd kernel, or the
    larger of the two backward kernels (bwd block_q below TQ is lifted to
    TQ exactly as the ops layer does)."""
    if kind == "fwd":
        return attn_fwd_vmem(block_q, block_kv, head_dim,
                             mask_mode=mask_mode, budget=budget)
    bq = max(int(block_q), TQ)
    ests = (attn_bwd_dq_vmem(bq, block_kv, head_dim, budget=budget),
            attn_bwd_dkv_vmem(bq, block_kv, head_dim, budget=budget))
    return max(ests, key=lambda e: e.total_bytes)


# ------------------------------------------------------------------ checks
def check_attn_blocks(block_q: int, block_kv: int, head_dim: int, *,
                      kinds: Sequence[str] = ("fwd", "bwd"),
                      mask_mode: str = "causal",
                      budget: Optional[int] = None,
                      label: str = "attention blocks") -> List[VmemEstimate]:
    """Raise ValueError (with the modeled footprint) when the blocks
    exceed the VMEM budget for any requested kernel kind.  Returns the
    per-kind estimates when everything fits."""
    ests = []
    for kind in kinds:
        est = attn_vmem(kind, block_q, block_kv, head_dim,
                        mask_mode=mask_mode, budget=budget)
        if not est.fits:
            raise ValueError(
                f"{label} exceed the analytic VMEM model: "
                f"{est.describe()}. Shrink attn_block_kv/attn_block_q "
                f"(or leave them unset to resolve through the autotuner "
                f"winners table).")
        ests.append(est)
    return ests


def check_gemm_blocks(bm: int, bk: int, bn: int, *, dims: str = "nn",
                      budget: Optional[int] = None,
                      label: str = "GEMM blocks") -> VmemEstimate:
    """Raise ValueError (with the modeled footprint) when a GEMM block
    config exceeds the VMEM budget."""
    est = gemm_vmem(bm, bk, bn, dims=dims, budget=budget)
    if not est.fits:
        raise ValueError(
            f"{label} exceed the analytic VMEM model: {est.describe()}.")
    return est


# ----------------------------------------------------------------- pruning
def prune_gemm_candidates(cands: Sequence[Tuple[int, int, int]], *,
                          dims: str = "nn",
                          mn: Optional[Tuple[int, int]] = None,
                          budget: Optional[int] = None
                          ) -> Tuple[list, List[dict]]:
    """Split GEMM sweep candidates into (kept, pruned).  With the logical
    output `mn`, each candidate's operand blocks are counted at the width
    the ops layer gives them (`gemm_operand_bytes`); without it, as fp8.
    `pruned` entries carry the modeled footprint so the sweep can record
    WHAT it skipped and WHY (no silent caps)."""
    kept, pruned = [], []
    for c in cands:
        widths = gemm_operand_bytes(*mn, c[0], c[2]) if mn else (1, 1)
        est = gemm_vmem(*c, dims=dims, a_bytes=widths[0],
                        b_bytes=widths[1], budget=budget)
        if est.fits:
            kept.append(c)
        else:
            pruned.append({"blocks": list(c),
                           "vmem_bytes": est.total_bytes,
                           "budget_bytes": est.budget_bytes,
                           "reason": "modeled VMEM exceeds budget"})
    return kept, pruned


def prune_attn_candidates(kind: str, cands: Sequence[Tuple[int, int]],
                          head_dim: int, *, mask_mode: str = "causal",
                          budget: Optional[int] = None
                          ) -> Tuple[list, List[dict]]:
    """Split attention sweep candidates into (kept, pruned) — same
    contract as `prune_gemm_candidates`."""
    kept, pruned = [], []
    for bq, bkv in cands:
        est = attn_vmem(kind, bq, bkv, head_dim, mask_mode=mask_mode,
                        budget=budget)
        if est.fits:
            kept.append((bq, bkv))
        else:
            pruned.append({"blocks": [bq, bkv],
                           "vmem_bytes": est.total_bytes,
                           "budget_bytes": est.budget_bytes,
                           "reason": "modeled VMEM exceeds budget"})
    return kept, pruned
