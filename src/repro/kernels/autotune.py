"""Per-(shape, dtype, layout) block-size autotuner for the fused kernels.

The hand-picked block constants (fused GEMM DEFAULT_BM/BK/BN, attention
DEFAULT_BQ/DEFAULT_BKV) were tuned for large shapes and *lose* wall-clock
at small ones (BENCH_kernels.json: fused/unfused GEMM 0.88, attention 0.95
at s=256).  This module closes that gap:

 * it sweeps candidate block configs per (shape-bucket, layout, format)
   key and times each candidate on a *blocked XLA analogue* of the kernel
   schedule — the same dataflow the Pallas kernel executes (tile dots with
   f32 accumulation, quantize-in-epilogue, amax read once from the
   quantized tile).  The single-read amax is modelled as the kernel
   computes it: a 1-byte bit-pattern reduce (`fp8_amax_bits`) off the
   materialized quantized tile, never a float upcast-abs-max over the
   producer (XLA CPU would re-run the quantize inside the reduce loop and
   bill the kernel dataflow for work it never does);

 * every winner is gated on a bit-exact parity check of the REAL kernel
   (interpret mode) against the ref.py oracle before it is persisted —
   the autotuner can never record a config the kernel won't honor;

 * winners land in a JSON table consulted by the ops-layer entry points
   (`fused_quant_matmul`, `fp8_matmul`, `fp8_attention_fwd/bwd`) and by
   `launch/specs.py`.  Explicit knobs always win over the table; the table
   wins over the built-in defaults.  Correctness never depends on the
   table: results are bit-invariant to every valid block config (the
   streamed-invariance law), so a stale or foreign table can only change
   speed, never bits.

Entries that carry a `device` key were timed on that chip with the real
fused kernel in the form the ops layer runs it (operands widened per
`fused_quant_matmul.kernel.widened_operands`), against the fp8 operands at
the built-in defaults (`default_wall_us`), with bk held at the default so
the output bits match it; the others come from the CPU analogue sweep.

Table location: `src/repro/kernels/autotune_table.json` (shipped with the
repo), overridable via `$REPRO_AUTOTUNE_TABLE`.  The `autotune` knob on
the ops (and `QuantConfig.autotune`) is `"table"` (consult the default
table), `"off"` (built-in defaults only), or a path to an alternative
table.  Ops resolve at trace time, so an in-process table edit is picked
up on the next new-shape trace, not for already-traced shapes.

Shape keys bucket each dim to the next power of two so neighbouring sizes
share an entry:

    gemm.{nn|nt|tn}.{e5m2|e4m3}.m{M}_k{K}_n{N}
    attn.{fwd|bwd}.{mask_mode}.q{Q}_s{S}_d{D}

CLI:  python -m repro.kernels.autotune [--smoke] [--table PATH]
      (sweeps, prints a report, and writes winners to the table).
"""
from __future__ import annotations

import functools
import json
import os
import threading
from pathlib import Path

LANE = 128   # fp8 lane width shared by every kernel in this package
TQ = 128     # backward dK/dV contraction granularity (fp8_attention)

DEFAULT_TABLE = Path(__file__).with_name("autotune_table.json")
ENV_VAR = "REPRO_AUTOTUNE_TABLE"

_CACHE: dict = {}
_CACHE_LOCK = threading.Lock()


# --------------------------------------------------------------- table I/O
def table_path(autotune: str = "table"):
    """Map the `autotune` knob to a table path (None = don't consult)."""
    if autotune == "off":
        return None
    if autotune == "table":
        return Path(os.environ.get(ENV_VAR) or DEFAULT_TABLE)
    return Path(autotune)


def load_table(path) -> dict:
    """mtime-cached JSON load; a missing or malformed table reads empty
    (the table is advisory — it must never be able to break a run)."""
    if path is None:
        return {}
    path = Path(path)
    try:
        mtime = path.stat().st_mtime_ns
    except OSError:
        return {}
    with _CACHE_LOCK:
        hit = _CACHE.get(str(path))
        if hit is not None and hit[0] == mtime:
            return hit[1]
    try:
        table = json.loads(path.read_text())
    except (OSError, ValueError):
        return {}
    if not isinstance(table, dict):
        return {}
    with _CACHE_LOCK:
        _CACHE[str(path)] = (mtime, table)
    return table


def save_table(path, table: dict):
    """Atomic write (tmp + rename) + read-cache invalidation."""
    path = Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_name(path.name + f".tmp{os.getpid()}")
    tmp.write_text(json.dumps(table, indent=1, sort_keys=True) + "\n")
    tmp.rename(path)
    with _CACHE_LOCK:
        _CACHE.pop(str(path), None)


# ------------------------------------------------------------------- keys
def _bucket(n) -> int:
    b = 8
    while b < max(int(n), 1):
        b *= 2
    return b


def gemm_key(dims: str, m: int, k: int, n: int,
             out_format: str = "e5m2") -> str:
    return (f"gemm.{dims}.{out_format}."
            f"m{_bucket(m)}_k{_bucket(k)}_n{_bucket(n)}")


def attn_key(kind: str, mask_mode: str, q_len: int, s_len: int,
             d: int) -> str:
    return (f"attn.{kind}.{mask_mode}."
            f"q{_bucket(q_len)}_s{_bucket(s_len)}_d{_bucket(d)}")


# -------------------------------------------------------------- resolution
def _table_int(entry, key):
    v = entry.get(key) if isinstance(entry, dict) else None
    return int(v) if isinstance(v, int) and not isinstance(v, bool) \
        and v > 0 else None


def resolve_gemm_blocks(dims, m, k, n, *, out_format="e5m2",
                        bm=None, bk=None, bn=None, autotune="table",
                        defaults):
    """Effective (bm, bk, bn) for a GEMM call.  Per-knob precedence:
    explicit int > table entry > built-in default (`defaults` triple).
    Explicit knobs must be positive — no silent correction."""
    for name, v in (("bm", bm), ("bk", bk), ("bn", bn)):
        if v is not None and v <= 0:
            raise ValueError(f"explicit {name} must be positive, got {v}")
    entry = {}
    if autotune != "off" and (bm is None or bk is None or bn is None):
        entry = load_table(table_path(autotune)).get(
            gemm_key(dims, m, k, n, out_format), {})
    dbm, dbk, dbn = defaults
    bm = bm if bm is not None else (_table_int(entry, "bm") or dbm)
    bk = bk if bk is not None else (_table_int(entry, "bk") or dbk)
    bn = bn if bn is not None else (_table_int(entry, "bn") or dbn)
    return int(bm), int(bk), int(bn)


def _valid_block_q(kind, bq):
    if bq is None or bq <= 0:
        return False
    if kind == "bwd":
        return bq >= TQ and bq % TQ == 0
    return bq <= TQ or bq % TQ == 0


def resolve_attn_blocks(kind, mask_mode, q_len, s_len, d, *,
                        block_q=None, block_kv=None, autotune="table"):
    """Effective (block_q, block_kv) for an attention call; block_kv may
    resolve to None (downstream ref.resolve_block_kv applies the kernel
    default).  Explicit knobs the kernel cannot honor raise instead of
    being silently clamped: backward block_q is pinned to TQ multiples
    (dK/dV contraction granularity) and forward block_q above TQ must be
    a TQ multiple.  Table entries failing the same checks are ignored."""
    if block_q is not None and not _valid_block_q(kind, block_q):
        if kind == "bwd":
            raise ValueError(
                f"backward block_q must be a positive multiple of "
                f"TQ={TQ} (dK/dV contraction granularity), got {block_q}")
        raise ValueError(
            f"block_q must be positive and a multiple of {TQ} when "
            f"larger than {TQ}, got {block_q}")
    if block_kv is not None and (block_kv <= 0 or block_kv % LANE):
        raise ValueError(
            f"block_kv must be a positive multiple of {LANE}, "
            f"got {block_kv}")
    entry = {}
    if autotune != "off" and (block_q is None or block_kv is None):
        entry = load_table(table_path(autotune)).get(
            attn_key(kind, mask_mode, q_len, s_len, d), {})
    bq = block_q
    if bq is None:
        tv = _table_int(entry, "block_q")
        bq = tv if _valid_block_q(kind, tv) else TQ
    bkv = block_kv
    if bkv is None:
        tv = _table_int(entry, "block_kv")
        bkv = tv if tv is not None and tv % LANE == 0 else None
    return int(bq), bkv


# ------------------------------------------------- blocked timing analogues
# The sweep runs on whatever backend the process has (CI: CPU).  Pallas
# interpret-mode walls only measure the interpreter, so candidates are
# timed on blocked XLA programs with the kernel's dataflow instead: block
# shape genuinely moves the wall (loop trip counts, cache blocking,
# fusion extents) the same way it moves the kernel's schedule.

def _bench(fn, *args, iters=20, reps=5):
    """Best-of-`reps` mean wall of `iters` calls, in microseconds."""
    import time

    import jax
    out = fn(*args)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / iters * 1e6)
    return best


def make_gemm_analogue(m, k, n, *, dims="nn", bm, bk, bn,
                       out_format="e5m2"):
    """Blocked analogue of the fused quantize-epilogue GEMM: (bm, bn)
    output tiles, bk-stepped f32 accumulation, SR quantize in the
    epilogue — all one program, so the f32 accumulator never round-trips
    HBM between the GEMM and the Q pass. The amax observation is a
    separate 1-byte bit-pattern reduce over the quantized payload,
    modelled IDENTICALLY to the unfused side's amax pass: in the kernel
    it's a grid-unit scalar accumulated from VMEM-resident bits (free),
    and folding it into this program instead would bill the fused
    dataflow for XLA CPU's in-program reduce codegen — work the kernel
    never does. Keeping the amax program symmetric on both sides leaves
    the measured difference to what the fused epilogue actually
    eliminates: the materialized f32 intermediate and the separate
    Q-pass dispatch."""
    import jax
    import jax.numpy as jnp

    from repro.core.fp8_formats import get_format
    from repro.core.quantize import fp8_amax_bits, sr_fp8_via_f16
    fmt = get_format(out_format)
    bm, bk, bn = min(bm, m), min(bk, k), min(bn, n)

    def tile_dot(a8, b8, i0, j0, k0):
        if dims == "nn":
            at, bt = a8[i0:i0 + bm, k0:k0 + bk], b8[k0:k0 + bk, j0:j0 + bn]
        elif dims == "nt":
            at, bt = a8[i0:i0 + bm, k0:k0 + bk], b8[j0:j0 + bn, k0:k0 + bk].T
        else:  # "tn"
            at, bt = a8[k0:k0 + bk, i0:i0 + bm].T, b8[k0:k0 + bk, j0:j0 + bn]
        return jax.lax.dot_general(
            at.astype(jnp.bfloat16), bt.astype(jnp.bfloat16),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @jax.jit
    def dot_quant(a8, b8, rand8, scale):
        inv = 1.0 / scale
        rows = []
        for i0 in range(0, m, bm):
            cols = []
            for j0 in range(0, n, bn):
                # No zeros-init accumulator: the kernel's VMEM scratch is
                # written by the first k-step, and a materialized zeros +
                # add is an extra full-tile pass XLA CPU does not elide.
                parts = [tile_dot(a8, b8, i0, j0, k0)
                         for k0 in range(0, k, bk)]
                acc = functools.reduce(lambda x, y: x + y, parts)
                cols.append(sr_fp8_via_f16(
                    acc * inv, rand8[i0:i0 + bm, j0:j0 + bn], fmt))
            rows.append(cols[0] if len(cols) == 1
                        else jnp.concatenate(cols, axis=1))
        # Single-tile configs skip the concatenate: XLA materializes a
        # concat of one operand as a full copy.
        return rows[0] if len(rows) == 1 else jnp.concatenate(rows, axis=0)

    amax_bits = jax.jit(fp8_amax_bits)

    def f(a8, b8, rand8, scale):
        q = dot_quant(a8, b8, rand8, scale)
        return q, amax_bits(q)

    return f


def make_attn_analogue(s, d, *, bq, bkv, passes=1, fmt="e5m2"):
    """Blocked analogue of the causal fused-attention forward over
    (B, S, D) flattened heads. Each q-tile row of bq queries visits the
    kv-stripes the kernel's causal block maps visit — the strip
    [0, roundup(i0 + bq, bkv)), stripe-granular like the kernel, so
    coarser bkv honestly costs more over-diagonal work. passes=1 is the
    one-pass schedule: each score strip is computed once and consumed
    once. passes=2 is the retired two-pass schedule: an extra (m, l)
    score pass re-computes every strip first — the wall ratio of the two
    is the honest cost of that extra pass.

    Structure is a pipeline of small jitted programs per row (score dot
    + mask + S quantize | softmax + P quantize + PV), with tile offsets
    static so masks fold to constants and slicing happens in-jit — an
    eager slice or scalar on this host is a full dispatch (~100µs+) on
    its own. This mirrors the separately-jitted passes of the unfused
    side so per-element codegen is comparable and the measured
    difference is the dataflow: causal strip skipping, single-visit
    scores, and row-strip (never (S, S)) intermediates. One big jitted
    program would be unfaithful the other way — XLA CPU re-runs fused
    producers inside downstream float reduces, billing the kernel
    dataflow for work it never does. For the same reason amaxes are
    1-byte bit-pattern reduces off materialized inputs; the P amax uses
    the softmax identity max(e) = exp(rowmax(xx) - m) = 1 computed from
    the already-reduced m rather than a reduce over the in-jit e (which
    would re-run the exp chain inside the reduce loop).

    The per-row online (m, l, acc) rescale the real kernel carries
    across stripes is per-lane scalar work; the analogue folds it into
    one strip-level softmax per row, which preserves per-element visit
    counts and memory traffic — the quantities this cost model ranks
    block sizes by."""
    import functools

    import jax
    import jax.numpy as jnp

    from repro.core.fp8_formats import get_format
    from repro.core.quantize import fp8_amax_bits, quantize_rne
    fmt_ = get_format(fmt)
    bq, bkv = min(bq, s), min(bkv, s)

    def _hi(i0):
        # Columns visited for the row at i0: stripe-granular roundup.
        return min(-(-(i0 + bq) // bkv) * bkv, s)

    def _mask(i0, hi):
        # Static offsets: the comparison folds to a constant mask.
        rows = i0 + jnp.arange(bq)[None, :, None]
        cols = jnp.arange(hi)[None, None, :]
        return cols <= rows

    @functools.partial(jax.jit, static_argnums=(0,))
    def score_row(i0, q8, k8):
        hi = _hi(i0)
        x = jax.lax.dot_general(
            q8[:, i0:i0 + bq].astype(jnp.bfloat16),
            k8[:, :hi].astype(jnp.bfloat16),
            (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        return quantize_rne(jnp.where(_mask(i0, hi), x, 0.0), fmt_)

    @functools.partial(jax.jit, static_argnums=(0,))
    def ml_row(i0, s8):
        # passes=2 first pass: (m, l) only, no PV work.
        xx = jnp.where(_mask(i0, _hi(i0)), s8.astype(jnp.float32), -1e30)
        m = jnp.max(xx, -1, keepdims=True)
        return m, jnp.sum(jnp.exp(xx - m), -1, keepdims=True)

    @functools.partial(jax.jit, static_argnums=(0,))
    def consume_row(i0, s8, v8):
        hi = _hi(i0)
        am_s = fp8_amax_bits(s8)
        xx = jnp.where(_mask(i0, hi), s8.astype(jnp.float32), -1e30)
        m = jnp.max(xx, -1, keepdims=True)
        e = jnp.exp(xx - m)      # masked: exp(-1e30 - m) flushes to 0
        p8 = quantize_rne(e, fmt_)
        am_p = fp8_amax_bits(quantize_rne(
            jnp.max(jnp.exp(jnp.max(xx, -1, keepdims=True) - m)), fmt_))
        l = jnp.sum(e, -1, keepdims=True)
        o = jax.lax.dot_general(
            p8.astype(jnp.bfloat16),
            v8[:, :hi].astype(jnp.bfloat16),
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        return ((o / jnp.where(l > 0, l, 1.0)).astype(jnp.bfloat16),
                am_s, am_p)

    @jax.jit
    def epilogue(outs, am_s, am_p):
        return (jnp.concatenate(outs, axis=1),
                jnp.max(jnp.stack(am_s)), jnp.max(jnp.stack(am_p)))

    def f(q8, k8, v8):
        outs, am_s, am_p = [], [], []
        for i0 in range(0, s, bq):
            if passes == 2:
                r = ml_row(i0, score_row(i0, q8, k8))
                jax.tree_util.tree_map(
                    lambda x: x.block_until_ready(), r)
            o, a_s, a_p = consume_row(i0, score_row(i0, q8, k8), v8)
            outs.append(o)
            am_s.append(a_s)
            am_p.append(a_p)
        return epilogue(tuple(outs), tuple(am_s), tuple(am_p))

    return f


def make_attn_bwd_analogue(s, d, *, bq, bkv, fmt="e5m2"):
    """Jitted blocked analogue of the dQ backward schedule for one head:
    per (q-tile, stripe) recompute scores -> P, form dP = dO.V^T and
    dS = P*(dP - delta), quantize both (amax read once), accumulate
    dQ += dS.K — the per-stripe op mix of the real dq kernel body."""
    import jax
    import jax.numpy as jnp

    from repro.core.fp8_formats import get_format
    from repro.core.quantize import fp8_amax_bits, quantize_rne
    fmt_ = get_format(fmt)
    bq, bkv = min(bq, s), min(bkv, s)

    def f(q8, k8, v8, do):
        amax_dp = jnp.float32(0)
        amax_ds = jnp.float32(0)
        outs = []
        for i0 in range(0, s, bq):
            hi = i0 + bq
            dq = jnp.zeros((bq, d), jnp.float32)
            dot = jnp.zeros((bq, 1), jnp.float32)
            for j0 in range(0, hi, bkv):
                x = jax.lax.dot_general(
                    q8[i0:i0 + bq].astype(jnp.bfloat16),
                    k8[j0:j0 + bkv].astype(jnp.bfloat16),
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                rows = i0 + jnp.arange(bq)[:, None]
                cols = j0 + jnp.arange(bkv)[None, :]
                valid = cols <= rows
                p = jnp.where(valid, jnp.exp(x - jnp.max(
                    x, -1, keepdims=True)), 0.0)
                dp = jax.lax.dot_general(
                    do[i0:i0 + bq].astype(jnp.bfloat16),
                    v8[j0:j0 + bkv].astype(jnp.bfloat16),
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                dp8 = quantize_rne(dp, fmt_)
                dp8 = jax.lax.optimization_barrier(dp8)
                amax_dp = jnp.maximum(amax_dp, fp8_amax_bits(dp8))
                ds = p * (dp8.astype(jnp.float32) - dot)
                ds8 = quantize_rne(ds, fmt_)
                ds8 = jax.lax.optimization_barrier(ds8)
                amax_ds = jnp.maximum(amax_ds, fp8_amax_bits(ds8))
                dq = dq + jax.lax.dot_general(
                    ds8.astype(jnp.bfloat16),
                    k8[j0:j0 + bkv].astype(jnp.bfloat16),
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            outs.append(dq)
        return jnp.concatenate(outs, axis=0), amax_dp, amax_ds

    return jax.jit(f)


# ------------------------------------------------------------------ sweeps
def gemm_candidates(m, k, n, *, defaults, smoke=False):
    """Candidate (bm, bk, bn) triples for a shape: always includes the
    built-in default (so tuned-vs-default >= 1.0 by construction) and the
    whole-shape single block; deduped after the ops-layer clamps."""
    raw = [defaults, (m, k, n), (128, 128, 128)]
    if not smoke:
        raw += [(128, 256, 256), (256, 256, 256), (256, 512, 256),
                (512, 512, 512), (128, 512, 512)]
    out, seen = [], set()
    for bm, bk, bn in raw:
        c = (min(bm, max(8, m)), min(bk, max(128, k)),
             min(bn, max(128, n)))
        if c not in seen:
            seen.add(c)
            out.append(c)
    return out


def attn_candidates(kind, q_len, s_len, *, smoke=False):
    """Candidate (block_q, block_kv) pairs — only configs the kernel
    honors (bwd block_q pinned to TQ multiples)."""
    bqs = (64, 128, 256) if kind == "fwd" else (128, 256)
    bkvs = (128, 256, 512)
    if smoke:
        bqs = (64, 128) if kind == "fwd" else (128,)
        bkvs = (128, 512)
    out, seen = [], set()
    for bq in bqs:
        for bkv in bkvs:
            c = (min(bq, max(1 if kind == "fwd" else TQ, q_len)),
                 min(bkv, -(-max(s_len, 1) // LANE) * LANE))
            if _valid_block_q(kind, c[0]) and c not in seen:
                seen.add(c)
                out.append(c)
    return out


def _gemm_parity(m, k, n, dims, out_format, bm, bk, bn):
    """Bit-check the real fused kernel (interpret) against its oracle at
    this block config; raises on any mismatch."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.fused_quant_matmul import (fused_quant_matmul,
                                                  fused_quant_matmul_ref)
    shapes = {"nn": ((m, k), (k, n)), "nt": ((m, k), (n, k)),
              "tn": ((k, m), (k, n))}[dims]
    a8 = (jax.random.normal(jax.random.PRNGKey(0), shapes[0])
          * 0.25).astype(jnp.float8_e5m2)
    b8 = (jax.random.normal(jax.random.PRNGKey(1), shapes[1])
          * 0.1).astype(jnp.float8_e5m2)
    key = jax.random.PRNGKey(2)
    scale = jnp.ones((1,), jnp.float32) * 2.0
    got, ga = fused_quant_matmul(a8, b8, key, scale, dims=dims, bm=bm,
                                 bk=bk, bn=bn, out_format=out_format,
                                 with_amax=True, amax_units="grid",
                                 interpret=True)
    rand8 = jax.random.bits(key, (m, n), jnp.uint8)
    ref, ra = fused_quant_matmul_ref(a8, b8, rand8, scale, dims=dims,
                                     out_format=out_format, with_amax=True)
    np.testing.assert_array_equal(np.asarray(got).view(np.uint8),
                                  np.asarray(ref).view(np.uint8))
    assert float(ga) == float(ra), (float(ga), float(ra))


def _attn_parity(s, d, kind, bq, bkv, fmt):
    """Bit-check the real attention kernel (interpret) against the ref
    oracle at this block config; raises on any mismatch."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.fp8_attention import (fp8_attention_bwd,
                                             fp8_attention_bwd_ref,
                                             fp8_attention_fwd,
                                             fp8_attention_fwd_ref)
    dt = jnp.float8_e4m3fn if fmt == "e4m3" else jnp.float8_e5m2
    q8, k8, v8 = [(jax.random.normal(jax.random.PRNGKey(i), (1, 2, s, d))
                   * 0.3).astype(dt) for i in range(3)]
    seed = jnp.uint32(7)
    kw = dict(mask_mode="causal", fmt_s=fmt, fmt_p=fmt, rounding_s="sr",
              rounding_p="sr")
    if kind == "fwd":
        scal = jnp.array([0.5, 2.0, 8.0, 0.25], jnp.float32)
        o, a_s, a_p = fp8_attention_fwd(q8, k8, v8, seed, scal,
                                        block_q=bq, block_kv=bkv,
                                        interpret=True, **kw)
        ro, rs, rp, _, _ = fp8_attention_fwd_ref(q8, k8, v8, seed, scal,
                                                 block_kv=bkv, **kw)
        np.testing.assert_array_equal(
            np.asarray(o).view(np.uint16), np.asarray(ro).view(np.uint16))
        assert (float(a_s), float(a_p)) == (float(rs), float(rp))
    else:
        do8 = (jax.random.normal(jax.random.PRNGKey(4), (1, 2, s, d))
               * 0.2).astype(jnp.float8_e5m2)
        scal = jnp.array([0.5, 2.0, 8.0, 0.125, 0.7, 1.5, 0.3, 0.8, 0.9,
                          0.05], jnp.float32)
        kw.update(fmt_e="e5m2", rounding_e="sr", saturate_e=False)
        outs = fp8_attention_bwd(q8, k8, v8, do8, seed, scal, block_q=bq,
                                 block_kv=bkv, interpret=True, **kw)
        refs = fp8_attention_bwd_ref(q8, k8, v8, do8, seed, scal, **kw)
        for a, r in zip(outs[:3], refs[:3]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(r))
        assert (float(outs[3]), float(outs[4])) \
            == (float(refs[3]), float(refs[4]))


def sweep_gemm(shapes=None, *, dims_list=("nn", "nt", "tn"),
               out_format="e5m2", smoke=False, parity=True, table=None,
               iters=20, reps=5, log=print):
    """Time every candidate per (shape, dims), gate the winner on kernel
    parity, and return (table_entries, report_rows)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.fused_quant_matmul import kernel as _fk
    defaults = (_fk.DEFAULT_BM, _fk.DEFAULT_BK, _fk.DEFAULT_BN)
    if shapes is None:
        shapes = [(256, 256, 256)] if smoke \
            else [(256, 256, 256), (512, 512, 512), (1024, 1024, 1024)]
    table = {} if table is None else table
    report = []
    for m, k, n in shapes:
        a8 = (jax.random.normal(jax.random.PRNGKey(0), (m, k))
              * 0.25).astype(jnp.float8_e5m2)
        b8 = (jax.random.normal(jax.random.PRNGKey(1), (k, n))
              * 0.1).astype(jnp.float8_e5m2)
        rand8 = jax.random.bits(jax.random.PRNGKey(2), (m, n), jnp.uint8)
        scale = jnp.float32(2.0)
        for dims in dims_list:
            cands = gemm_candidates(m, k, n, defaults=defaults,
                                    smoke=smoke)
            # Analytic VMEM pre-filter: never time a candidate the model
            # proves can't fit.  The clamped default (cands[0]) is exempt
            # — it anchors tuned_vs_default — but gets a loud warning if
            # the model says it wouldn't fit either.
            from repro.analysis import vmem as _vm
            kept, pruned = _vm.prune_gemm_candidates(cands[1:], dims=dims,
                                                     mn=(m, n))
            widths = _vm.gemm_operand_bytes(m, n, cands[0][0], cands[0][2])
            if not _vm.gemm_vmem(*cands[0], dims=dims, a_bytes=widths[0],
                                 b_bytes=widths[1]).fits:
                log(f"[autotune] WARNING: default GEMM blocks "
                    f"{cands[0]} exceed the VMEM model for "
                    f"({m}, {k}, {n}) {dims}; timing it anyway as the "
                    f"baseline")
            for p in pruned:
                log(f"[autotune] prune {tuple(p['blocks'])} for "
                    f"({m}, {k}, {n}) {dims}: {p['reason']} "
                    f"({p['vmem_bytes']} > {p['budget_bytes']} bytes)")
            cands = [cands[0]] + kept
            walls = {}
            for bm, bk, bn in cands:
                fn = make_gemm_analogue(m, k, n, dims=dims, bm=bm, bk=bk,
                                        bn=bn, out_format=out_format)
                walls[(bm, bk, bn)] = _bench(fn, a8, b8, rand8, scale,
                                             iters=iters, reps=reps)
            default = cands[0]      # clamped built-in default, always first
            best = min(walls, key=walls.get)
            if parity:
                _gemm_parity(m, k, n, dims, out_format, *best)
            key = gemm_key(dims, m, k, n, out_format)
            table[key] = {
                "bm": best[0], "bk": best[1], "bn": best[2],
                "wall_us": round(walls[best], 2),
                "default_wall_us": round(walls[default], 2),
                "tuned_vs_default": round(walls[default] / walls[best], 4),
                "parity": "bitexact" if parity else "unchecked",
            }
            report.append({"key": key, "shape": [m, k, n], "dims": dims,
                           "candidates": {f"{c[0]}x{c[1]}x{c[2]}":
                                          round(w, 2)
                                          for c, w in walls.items()},
                           "pruned": pruned,
                           **table[key]})
            log(f"[autotune] {key}: tuned {best} "
                f"{walls[best]:.0f}us vs default {default} "
                f"{walls[default]:.0f}us "
                f"(x{walls[default] / walls[best]:.2f})")
    return table, report


def sweep_attention(shapes=None, *, kinds=("fwd", "bwd"),
                    mask_mode="causal", fmt="e5m2", smoke=False,
                    parity=True, table=None, iters=20, reps=5,
                    log=print):
    """Time every (block_q, block_kv) candidate per (s, d) and kind, gate
    winners on kernel parity, and return (table_entries, report_rows)."""
    import jax
    import jax.numpy as jnp
    if shapes is None:
        shapes = [(256, 64)] if smoke else [(256, 64), (512, 64),
                                            (1024, 128)]
    table = {} if table is None else table
    report = []
    for s, d in shapes:
        q8, k8, v8 = [(jax.random.normal(jax.random.PRNGKey(i), (s, d))
                       * 0.3).astype(jnp.float8_e5m2) for i in range(3)]
        do = (jax.random.normal(jax.random.PRNGKey(4), (s, d))
              * 0.2).astype(jnp.float8_e5m2)
        for kind in kinds:
            cands = attn_candidates(kind, s, s, smoke=smoke)
            # Analytic VMEM pre-filter (see sweep_gemm): can't-fit
            # candidates are logged + recorded, never timed.
            from repro.analysis import vmem as _vm
            kept, pruned = _vm.prune_attn_candidates(
                kind, cands, d, mask_mode=mask_mode)
            for p in pruned:
                log(f"[autotune] prune q{p['blocks'][0]}_kv"
                    f"{p['blocks'][1]} for ({s}, {d}) {kind}: "
                    f"{p['reason']} ({p['vmem_bytes']} > "
                    f"{p['budget_bytes']} bytes)")
            cands = kept
            walls = {}
            for bq, bkv in cands:
                if kind == "fwd":
                    fn = make_attn_analogue(s, d, bq=bq, bkv=bkv,
                                            passes=1, fmt=fmt)
                    walls[(bq, bkv)] = _bench(fn, q8[None], k8[None],
                                              v8[None], iters=iters,
                                              reps=reps)
                else:
                    fn = make_attn_bwd_analogue(s, d, bq=bq, bkv=bkv,
                                                fmt=fmt)
                    walls[(bq, bkv)] = _bench(fn, q8, k8, v8, do,
                                              iters=iters, reps=reps)
            from repro.kernels.fp8_attention import ref as _ar
            default = (min(TQ, s), _ar.resolve_block_kv(s, None))
            if not _vm.attn_vmem(kind, *default, d,
                                 mask_mode=mask_mode).fits:
                log(f"[autotune] WARNING: default attention blocks "
                    f"{default} exceed the VMEM model for ({s}, {d}) "
                    f"{kind}; timing them anyway as the baseline")
            if default not in walls:
                fn = (make_attn_analogue(s, d, bq=default[0],
                                         bkv=default[1], passes=1,
                                         fmt=fmt) if kind == "fwd" else
                      make_attn_bwd_analogue(s, d, bq=default[0],
                                             bkv=default[1], fmt=fmt))
                args_ = ((q8[None], k8[None], v8[None]) if kind == "fwd"
                         else (q8, k8, v8, do))
                walls[default] = _bench(fn, *args_, iters=iters, reps=reps)
            best = min(walls, key=walls.get)
            if parity:
                _attn_parity(s, d, kind, *best, fmt)
            key = attn_key(kind, mask_mode, s, s, d)
            table[key] = {
                "block_q": best[0], "block_kv": best[1],
                "wall_us": round(walls[best], 2),
                "default_wall_us": round(walls[default], 2),
                "tuned_vs_default": round(walls[default] / walls[best], 4),
                "parity": "bitexact" if parity else "unchecked",
            }
            report.append({"key": key, "shape": [s, d], "kind": kind,
                           "candidates": {f"q{c[0]}_kv{c[1]}": round(w, 2)
                                          for c, w in walls.items()},
                           "pruned": pruned,
                           **table[key]})
            log(f"[autotune] {key}: tuned {best} "
                f"{walls[best]:.0f}us vs default {default} "
                f"{walls[default]:.0f}us "
                f"(x{walls[default] / walls[best]:.2f})")
    return table, report


def run_sweep(*, smoke=False, table_file=None, parity=True, log=print):
    """Full sweep -> merge winners into the persisted table.  Returns the
    report rows (what kernel_bench records into BENCH_kernels.json)."""
    path = Path(table_file) if table_file is not None \
        else table_path("table")
    table = dict(load_table(path))
    _, rep_g = sweep_gemm(smoke=smoke, parity=parity, table=table,
                          log=log)
    _, rep_a = sweep_attention(smoke=smoke, parity=parity, table=table,
                               log=log)
    save_table(path, table)
    log(f"[autotune] wrote {len(table)} entries to {path}")
    return rep_g + rep_a


def main(argv=None):
    import argparse
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--smoke", action="store_true",
                   help="small shapes / few candidates (CI nightly)")
    p.add_argument("--table", default=None,
                   help=f"winners table path (default: $"
                        f"{ENV_VAR} or {DEFAULT_TABLE})")
    p.add_argument("--no-parity", action="store_true",
                   help="skip the interpret-mode winner parity gate")
    args = p.parse_args(argv)
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    run_sweep(smoke=args.smoke, table_file=args.table,
              parity=not args.no_parity)


if __name__ == "__main__":
    main()
