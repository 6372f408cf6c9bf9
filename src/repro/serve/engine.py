"""Batched serving engine: continuous batching over a fixed slot pool.

Inference runs the deterministic FP8 path (RNE, saturating — no stochastic
rounding at eval, per the paper's training/inference split) with an
optionally FP8-quantized KV cache (beyond-paper: decode is KV-bandwidth
bound; e5m2 KV halves the dominant roofline term).

Slot model: `max_batch` concurrent sequences. add_request() fills a free
slot (prefilling its cache region); step() decodes one token for every
active slot; finished sequences (EOS or max_len) free their slot. The jitted
decode step is shape-stable — request churn never recompiles.

Observability: prefill and decode run inside `obs.trace.Tracer` spans
(`repro.serve.<name>` on the profiler's clock), per-request prefill/decode
latencies and KV-slot occupancy accumulate into rolling windows, and
`stats()` snapshots the serving counters (latency percentiles, decode
tokens/s, occupancy) in the same jsonable shape the metrics pipeline and
`repro.tools.healthdash` consume.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig
from repro.models.transformer import init_stack_state
from repro.obs.trace import Tracer
from repro.train.step import make_serve_decode, make_serve_prefill

Array = jax.Array


@dataclasses.dataclass
class ServeConfig:
    max_batch: int = 8
    max_len: int = 512
    eos_id: int = -1          # -1 => never stops early
    temperature: float = 0.0  # 0 => greedy
    seed: int = 0


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray
    max_new_tokens: int
    generated: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # -- per-request telemetry (wall-clock, host side) -----------------------
    t_added: float = 0.0      # time.perf_counter() at add_request entry
    prefill_s: float = 0.0    # prefill latency (includes slot merge + sample)
    decode_s: float = 0.0     # summed decode-step share while active
    t_finished: float = 0.0   # perf_counter when the slot freed


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params, serve: ServeConfig,
                 frozen_scales: Optional[Dict[str, float]] = None,
                 frozen_formats: Optional[Dict[str, str]] = None):
        """frozen_scales: calibrated per-site scales (scaling.calibrate
        freeze/load_frozen) — enables deterministic calibrated FP8 inference;
        the FP8 KV cache consumes its per-layer scales from the same dict.

        frozen_formats: per-site storage formats the scales were calibrated
        under (scaling.calibrate freeze_with_formats / load_frozen_formats).
        When given, serving refuses to start if this engine's QuantConfig /
        KV-cache policy would quantize a site in a DIFFERENT format than it
        was calibrated for — a scale targeting the e4m3 grid is 128x off on
        the e5m2 grid, a silent-accuracy bug otherwise."""
        self.cfg = cfg
        self.params = params
        self.serve = serve
        self.frozen_scales = frozen_scales
        self.frozen_formats = frozen_formats
        if frozen_formats:
            self._check_formats(frozen_formats)
        self._prefill = jax.jit(make_serve_prefill(cfg, frozen_scales))
        self._decode = jax.jit(make_serve_decode(cfg, frozen_scales))
        b, ml = serve.max_batch, serve.max_len
        self.states = init_stack_state(cfg, b, max_len=ml,
                                       n_layers=cfg.n_layers)
        self.slots: List[Optional[Request]] = [None] * b
        self.positions = np.zeros((b,), np.int64)
        self.last_token = np.zeros((b,), np.int32)
        self._uid = 0
        # -- serving counters (host wall-clock; window bounds memory) --------
        self.tracer = Tracer("repro.serve")
        win = 512
        self._prefill_lat = collections.deque(maxlen=win)
        self._decode_lat = collections.deque(maxlen=win)
        self._req_lat = collections.deque(maxlen=win)
        self._occupancy = collections.deque(maxlen=win)
        self._n_requests = 0
        self._n_finished = 0
        self._prefill_tokens = 0
        self._decode_tokens = 0
        self._decode_time_s = 0.0

    def _check_formats(self, frozen_formats: Dict[str, str]):
        from repro.scaling.state import format_for_site
        quant = self.cfg.policy.quant
        kv_fmt = self.cfg.policy.kv_cache_format
        for key, calibrated in frozen_formats.items():
            # the same site->format rule the freeze side used to record
            serving = format_for_site(key, quant, kv_fmt)
            if serving != calibrated:
                raise ValueError(
                    f"frozen scale for site {key!r} was calibrated under "
                    f"format {calibrated!r} but this engine would quantize "
                    f"it as {serving!r} (recipe={quant.recipe!r}, "
                    f"kv_cache_format={kv_fmt!r}); recalibrate or fix the "
                    "serving config")

    # -- slot management ------------------------------------------------------
    def free_slots(self) -> List[int]:
        return [i for i, s in enumerate(self.slots) if s is None]

    def add_request(self, prompt: np.ndarray,
                    max_new_tokens: int = 32) -> int:
        """Prefill `prompt` into a free slot; returns the request uid."""
        free = self.free_slots()
        if not free:
            raise RuntimeError("no free slots; call step() until one frees")
        slot = free[0]
        self._uid += 1
        req = Request(self._uid, np.asarray(prompt, np.int32),
                      max_new_tokens, t_added=time.perf_counter())
        self.slots[slot] = req
        # Prefill this slot: run a batch-1-style prefill into the slot's
        # cache rows (the whole batch is passed; only this slot's rows are
        # consumed by construction of the cache update).
        s = req.prompt.shape[0]
        tokens = np.zeros((len(self.slots), s), np.int32)
        tokens[slot] = req.prompt
        with self.tracer.span("prefill", uid=req.uid, tokens=s):
            logits, new_states = self._prefill(
                self.params, {"tokens": jnp.asarray(tokens)},
                self.states)
            # Merge: take the new cache rows for this slot only.
            self.states = _merge_slot(self.states, new_states, slot)
            self.positions[slot] = s
            nxt = self._sample(np.asarray(logits)[slot, -1])
        req.prefill_s = time.perf_counter() - req.t_added
        self._prefill_lat.append(req.prefill_s)
        self._n_requests += 1
        self._prefill_tokens += s
        self.last_token[slot] = nxt
        req.generated.append(int(nxt))
        return req.uid

    # -- decode ---------------------------------------------------------------
    def step(self) -> Dict[int, List[int]]:
        """One decode step for all active slots. Returns finished requests."""
        active = [i for i, s in enumerate(self.slots) if s is not None]
        if not active:
            return {}
        t0 = time.perf_counter()
        self._occupancy.append(len(active) / len(self.slots))
        tokens = jnp.asarray(self.last_token[:, None])
        positions = jnp.asarray(self.positions[:, None].astype(np.int32))
        with self.tracer.span("decode", active=len(active)):
            logits, self.states = self._decode(
                self.params, {"tokens": tokens, "positions": positions},
                self.states)
            logits = np.asarray(logits)[:, 0]
        dt = time.perf_counter() - t0
        self._decode_lat.append(dt)
        self._decode_time_s += dt
        self._decode_tokens += len(active)
        finished: Dict[int, List[int]] = {}
        for i in active:
            req = self.slots[i]
            req.decode_s += dt
            nxt = self._sample(logits[i])
            req.generated.append(int(nxt))
            self.positions[i] += 1
            self.last_token[i] = nxt
            hit_eos = (self.serve.eos_id >= 0 and nxt == self.serve.eos_id)
            if hit_eos or len(req.generated) >= req.max_new_tokens \
                    or self.positions[i] >= self.serve.max_len - 1:
                req.t_finished = time.perf_counter()
                req.done = True
                self._n_finished += 1
                self._req_lat.append(req.t_finished - req.t_added)
                finished[req.uid] = req.generated
                self.slots[i] = None
        return finished

    def run_to_completion(self, max_steps: int = 10_000) -> Dict[int, List[int]]:
        out: Dict[int, List[int]] = {}
        for _ in range(max_steps):
            out.update(self.step())
            if not any(self.slots):
                break
        return out

    # -- telemetry ------------------------------------------------------------
    def stats(self) -> Dict[str, Any]:
        """Snapshot of the serving counters (jsonable; shape documented in
        docs/metrics_schema.md, rendered by repro.tools.healthdash)."""
        def pct(win, q):
            return float(np.percentile(np.asarray(win), q)) if win else None
        return {
            "requests": self._n_requests,
            "finished": self._n_finished,
            "active": sum(s is not None for s in self.slots),
            "max_batch": len(self.slots),
            "kv_slot_occupancy": (float(np.mean(self._occupancy))
                                  if self._occupancy else 0.0),
            "prefill_tokens": self._prefill_tokens,
            "decode_tokens": self._decode_tokens,
            "decode_tokens_per_s": (self._decode_tokens / self._decode_time_s
                                    if self._decode_time_s > 0 else 0.0),
            "prefill_latency_s": {"p50": pct(self._prefill_lat, 50),
                                  "p99": pct(self._prefill_lat, 99)},
            "decode_step_s": {"p50": pct(self._decode_lat, 50),
                              "p99": pct(self._decode_lat, 99)},
            "request_latency_s": {"p50": pct(self._req_lat, 50),
                                  "p99": pct(self._req_lat, 99)},
        }

    def _sample(self, logits: np.ndarray) -> int:
        logits = logits[:self.cfg.vocab_size]
        if self.serve.temperature <= 0:
            return int(logits.argmax())
        p = np.exp((logits - logits.max()) / self.serve.temperature)
        p /= p.sum()
        rng = np.random.default_rng(self.serve.seed + self._uid)
        return int(rng.choice(len(p), p=p))


def _merge_slot(old_states, new_states, slot: int):
    """Take slot `slot`'s rows from new_states, keep others from old.

    The batch dim depends on the stack layout, so it is resolved from the
    state-dict KEY, not the leaf rank: scanned groups ("stack_*") stack a
    leading group dim => batch at dim 1; unscanned ("layer_*"/"rem_*")
    leaves put batch at dim 0. (Guessing from rank alone merged unscanned
    KV caches along their LENGTH axis — every slot kept only its first
    cached token and decode walked off garbage.)"""
    def merge_with(bdim):
        def merge(o, n):
            if o.ndim > bdim and o.shape == n.shape:
                idx = [slice(None)] * o.ndim
                idx[bdim] = slice(slot, slot + 1)
                return o.at[tuple(idx)].set(n[tuple(idx)])
            return n
        return merge
    out = {}
    for key in old_states:
        bdim = 1 if key.startswith("stack_") else 0
        out[key] = jax.tree_util.tree_map(merge_with(bdim), old_states[key],
                                          new_states[key])
    return out


# ===========================================================================
# Paged engine: block-table KV, chunked prefill, on-device sampling
# ===========================================================================

@dataclasses.dataclass
class PagedServeConfig:
    """Knobs for `PagedServeEngine`.

    max_batch:   concurrent request rows per step (static shape).
    max_len:     max logical sequence length per request.
    n_pages:     KV pool pages per layer (page 0 is the reserved trash
                 page, so `(n_pages - 1) * page_size` tokens are
                 allocatable). KV memory scales with THIS, not with
                 max_batch * max_len.
    page_size:   tokens per page.
    chunk_size:  prompt tokens prefillable per request per step; decode is
                 the 1-token special case of the same jitted step.
    temperature / top_k / top_p: sampling controls (temperature<=0 =>
                 greedy argmax). seed: base of the per-request PRNG
                 streams (seed + uid, folded with the per-request token
                 index — batch-layout invariant).
    prefix_cache: exact full-page prompt-prefix reuse (bitwise-safe only
                 because frozen-scale serving is deterministic).
    """
    max_batch: int = 8
    max_len: int = 512
    n_pages: int = 64
    page_size: int = 16
    chunk_size: int = 32
    eos_id: int = -1
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: int = 0
    prefix_cache: bool = True
    max_cache_entries: int = 128


@dataclasses.dataclass
class _PagedRequest:
    uid: int
    prompt: np.ndarray
    max_new_tokens: int
    table: list                 # block table: page ids, position-major
    prefill_pos: int = 0        # next prompt position to prefill
    pos: int = 0                # tokens materialized in KV so far
    generated: list = dataclasses.field(default_factory=list)
    cached_tokens: int = 0      # prompt tokens satisfied by the prefix cache
    t_added: float = 0.0
    prefill_s: float = 0.0
    t_finished: float = 0.0


class PagedServeEngine:
    """Production serving loop over a paged KV pool.

    One jitted fixed-shape `step()` serves every phase: each request row
    carries either a prompt chunk (up to `chunk_size` tokens) or a decode
    step (1 token) through the SAME compiled program — `mode='chunk'`
    attention with a block-table gather, per-row `[start, n_valid]` ragged
    bounds, and on-device sampling. The step's outputs are the updated KV
    pools and one sampled token id per row: logits never leave the device
    (no per-token host sync; the host reads only the (B,) token vector it
    needs for EOS/scheduling).

    Under frozen scales the token streams are bit-identical to the legacy
    fixed-slot `ServeEngine` (locked by tests/test_paging.py): with a bf16
    KV cache the FULL stream matches for any chunk size; with an FP8 KV
    cache the decode phase matches given the same cache payloads, while
    chunked prefill reads earlier chunks' FP8 payloads (the cache IS the
    attention input — legacy prefill attends raw bf16 K/V, a documented
    semantic difference of chunked prefill, not a bug).
    """

    def __init__(self, cfg: ModelConfig, params, serve: PagedServeConfig,
                 frozen_scales: Optional[Dict[str, float]] = None,
                 frozen_formats: Optional[Dict[str, str]] = None):
        from repro.models.transformer import init_paged_stack_state
        from repro.serve.paging import PageAllocator
        from repro.serve.prefix_cache import PrefixCache, scale_fingerprint
        from repro.serve import sampling as _sampling
        from repro.train.step import _eval_cfg, _maybe_frozen
        from repro.models.transformer import forward

        self.cfg = cfg
        self.params = params
        self.serve = serve
        self.frozen_scales = frozen_scales
        self.frozen_formats = frozen_formats
        if frozen_formats:
            ServeEngine._check_formats(self, frozen_formats)

        self.pager = PageAllocator(serve.n_pages, serve.page_size)
        psize = serve.page_size
        # Static gather width: every position a request can ever hold.
        self.capacity = -(-serve.max_len // psize) * psize
        self.states = init_paged_stack_state(cfg, self.pager.n_slots,
                                             n_layers=cfg.n_layers)
        self.prefix_cache = None
        if serve.prefix_cache:
            fp = scale_fingerprint(
                frozen_scales, frozen_formats,
                recipe=cfg.policy.quant.recipe,
                kv_format=cfg.policy.kv_cache_format)
            self.prefix_cache = PrefixCache(
                self.pager, fp, max_entries=serve.max_cache_entries)

        ecfg = _eval_cfg(cfg, frozen_scales)
        temperature, top_k, top_p = (serve.temperature, serve.top_k,
                                     serve.top_p)
        vocab = cfg.vocab_size

        def step_fn(params, states, batch):
            """The whole serving step: chunk attention + head + sampling.
            Returns (sampled (B,) int32, new_states) — NO vocab-dim output,
            which the jaxpr test asserts."""
            with _maybe_frozen(frozen_scales):
                page = {"write_slots": batch["write_slots"],
                        "read_slots": batch["read_slots"],
                        "slot_pos": batch["slot_pos"],
                        "chunk_pos": batch["chunk_pos"]}
                logits, new_states, _ = forward(
                    params, batch["tokens"], cfg=ecfg, mode="chunk",
                    states=states, positions=batch["positions"], page=page,
                    gather_rows=batch["last_row"])
            lg = logits[:, 0].astype(jnp.float32)
            # Padded-vocab columns are masked BEFORE argmax/sampling — the
            # on-device greedy then bit-matches the legacy host-side
            # `logits[:vocab].argmax()`.
            col = jnp.arange(lg.shape[-1])
            lg = jnp.where(col[None, :] < vocab, lg, jnp.float32(-1e30))
            keys = _sampling.row_keys(batch["seeds"], batch["steps"])
            tok = _sampling.sample(lg, keys, temperature=temperature,
                                   top_k=top_k, top_p=top_p)
            return tok, new_states

        self._step = jax.jit(step_fn)

        b = serve.max_batch
        self.slots: List[Optional[_PagedRequest]] = [None] * b
        self._uid = 0
        self.tracer = Tracer("repro.serve")
        win = 512
        self._prefill_lat = collections.deque(maxlen=win)
        self._step_lat = collections.deque(maxlen=win)
        self._req_lat = collections.deque(maxlen=win)
        self._occupancy = collections.deque(maxlen=win)
        self._n_requests = 0
        self._n_finished = 0
        self._prefill_tokens = 0
        self._decode_tokens = 0
        self._decode_time_s = 0.0

    # -- admission ----------------------------------------------------------

    def free_slots(self) -> List[int]:
        return [i for i, s in enumerate(self.slots) if s is None]

    def add_request(self, prompt: np.ndarray,
                    max_new_tokens: int = 32) -> int:
        """Admit a request (prefill happens inside subsequent step()s).
        Raises `PagesExhausted` when the prompt needs more KV pages than
        the pool can allocate (after shedding LRU prefix-cache entries) —
        a structured refusal, never a silent truncation."""
        from repro.serve.paging import PagesExhausted
        free = self.free_slots()
        if not free:
            raise RuntimeError("no free slots; call step() until one frees")
        prompt = np.asarray(prompt, np.int32)
        n = int(prompt.shape[0])
        if n < 1 or n >= self.serve.max_len:
            raise ValueError(
                f"prompt length {n} out of range [1, {self.serve.max_len})")
        slot = free[0]
        self._uid += 1
        req = _PagedRequest(self._uid, prompt, max_new_tokens, table=[],
                            t_added=time.perf_counter())
        # Exact prefix reuse: splice cached full pages, prefill the rest.
        if self.prefix_cache is not None:
            pages, n_cached = self.prefix_cache.lookup(prompt)
            req.table = pages
            req.prefill_pos = req.pos = n_cached
            req.cached_tokens = n_cached
        need = self.pager.pages_for(n) - len(req.table)
        try:
            if need > self.pager.n_free and self.prefix_cache is not None:
                self.prefix_cache.evict_for(need)
            req.table += self.pager.alloc(max(need, 0),
                                          what=f"prompt of {n} tokens")
        except PagesExhausted:
            if req.cached_tokens:
                self.pager.release(req.table)   # undo the lookup retain
            raise
        self.slots[slot] = req
        self._n_requests += 1
        return req.uid

    # -- the unified step ---------------------------------------------------

    def _grow(self, req: _PagedRequest, pos: int):
        """Ensure `pos` is backed by a page (decode growth)."""
        from repro.serve.paging import PagesExhausted
        pageno = pos // self.serve.page_size
        if pageno < len(req.table):
            return
        try:
            req.table += self.pager.alloc(1, what=f"decode of req {req.uid}")
        except PagesExhausted:
            if self.prefix_cache is None or \
                    not self.prefix_cache.evict_for(1):
                raise
            req.table += self.pager.alloc(
                1, what=f"decode of req {req.uid}")

    def step(self) -> Dict[int, List[int]]:
        """One fixed-shape step: a prompt chunk OR one decode token per
        active row, interleaved freely. Returns finished requests."""
        from repro.serve import paging as _paging
        active = [i for i, s in enumerate(self.slots) if s is not None]
        if not active:
            return {}
        t0 = time.perf_counter()
        self._occupancy.append(len(active) / len(self.slots))
        b, tchunk, cap = (self.serve.max_batch, self.serve.chunk_size,
                          self.capacity)
        psize = self.serve.page_size
        tokens = np.zeros((b, tchunk), np.int32)
        positions = np.zeros((b, tchunk), np.int32)
        write_slots = np.zeros((b, tchunk), np.int32)
        chunk_pos = np.zeros((b, 2), np.int32)
        last_row = np.zeros((b,), np.int32)
        seeds = np.zeros((b,), np.int32)
        steps = np.zeros((b,), np.int32)
        tables, lengths = [], []
        plan = {}   # row -> ("prefill", t_eff) | ("decode",)
        n_prefill_rows = n_decode_rows = 0
        for i in range(b):
            req = self.slots[i]
            if req is None:
                tables.append([])
                lengths.append(0)
                continue
            seeds[i] = self.serve.seed + req.uid
            steps[i] = len(req.generated)
            if req.prefill_pos < len(req.prompt):
                pp = req.prefill_pos
                t_eff = min(tchunk, len(req.prompt) - pp)
                tokens[i, :t_eff] = req.prompt[pp:pp + t_eff]
                positions[i] = pp + np.arange(tchunk)
                write_slots[i, :t_eff] = _paging.flat_slots(
                    req.table, psize, pp, t_eff)
                chunk_pos[i] = (pp, t_eff)
                last_row[i] = t_eff - 1
                lengths.append(pp + t_eff)
                plan[i] = ("prefill", t_eff)
                n_prefill_rows += 1
            else:
                pos = req.pos
                self._grow(req, pos)
                tokens[i, 0] = (req.generated[-1] if req.generated
                                else req.prompt[-1])
                positions[i] = pos + np.arange(tchunk)
                write_slots[i, 0] = _paging.flat_slots(
                    req.table, psize, pos, 1)[0]
                chunk_pos[i] = (pos, 1)
                last_row[i] = 0
                lengths.append(pos + 1)
                plan[i] = ("decode",)
                n_decode_rows += 1
            tables.append(req.table)
        read_slots, slot_pos = _paging.gather_plan(tables, lengths, psize,
                                                   cap)
        batch = {"tokens": jnp.asarray(tokens),
                 "positions": jnp.asarray(positions),
                 "write_slots": jnp.asarray(write_slots),
                 "read_slots": jnp.asarray(read_slots),
                 "slot_pos": jnp.asarray(slot_pos),
                 "chunk_pos": jnp.asarray(chunk_pos),
                 "last_row": jnp.asarray(last_row),
                 "seeds": jnp.asarray(seeds),
                 "steps": jnp.asarray(steps)}
        with self.tracer.span("step", prefill_rows=n_prefill_rows,
                              decode_rows=n_decode_rows):
            tok, self.states = self._step(self.params, self.states, batch)
            tok = np.asarray(tok)          # (B,) int32 — the ONLY sync
        dt = time.perf_counter() - t0
        self._step_lat.append(dt)
        finished: Dict[int, List[int]] = {}
        for i, what in plan.items():
            req = self.slots[i]
            if what[0] == "prefill":
                t_eff = what[1]
                req.prefill_pos += t_eff
                req.pos = req.prefill_pos
                self._prefill_tokens += t_eff
                if req.prefill_pos < len(req.prompt):
                    continue            # prompt not done; sample discarded
                req.prefill_s = time.perf_counter() - req.t_added
                self._prefill_lat.append(req.prefill_s)
                if self.prefix_cache is not None:
                    self.prefix_cache.insert(req.prompt, req.table)
            else:
                req.pos += 1
                self._decode_tokens += 1
                self._decode_time_s += dt / max(len(plan), 1)
            nxt = int(tok[i])
            req.generated.append(nxt)
            hit_eos = (self.serve.eos_id >= 0 and nxt == self.serve.eos_id)
            if hit_eos or len(req.generated) >= req.max_new_tokens \
                    or req.pos >= self.serve.max_len - 1:
                req.t_finished = time.perf_counter()
                self._n_finished += 1
                self._req_lat.append(req.t_finished - req.t_added)
                finished[req.uid] = req.generated
                self.pager.release(req.table)
                self.slots[i] = None
        return finished

    def run_to_completion(self,
                          max_steps: int = 10_000) -> Dict[int, List[int]]:
        out: Dict[int, List[int]] = {}
        for _ in range(max_steps):
            out.update(self.step())
            if not any(s is not None for s in self.slots):
                break
        return out

    # -- telemetry ----------------------------------------------------------

    def stats(self) -> Dict[str, Any]:
        """Serving counters + page-pool occupancy + prefix-cache hit rate
        (jsonable, same shape family as the legacy engine's stats())."""
        def pct(win, q):
            return float(np.percentile(np.asarray(win), q)) if win else None
        out = {
            "requests": self._n_requests,
            "finished": self._n_finished,
            "active": sum(s is not None for s in self.slots),
            "max_batch": len(self.slots),
            "slot_occupancy": (float(np.mean(self._occupancy))
                               if self._occupancy else 0.0),
            "prefill_tokens": self._prefill_tokens,
            "decode_tokens": self._decode_tokens,
            "decode_tokens_per_s": (self._decode_tokens / self._decode_time_s
                                    if self._decode_time_s > 0 else 0.0),
            "prefill_latency_s": {"p50": pct(self._prefill_lat, 50),
                                  "p99": pct(self._prefill_lat, 99)},
            "step_s": {"p50": pct(self._step_lat, 50),
                       "p99": pct(self._step_lat, 99)},
            "request_latency_s": {"p50": pct(self._req_lat, 50),
                                  "p99": pct(self._req_lat, 99)},
        }
        out.update(self.pager.stats())
        if self.prefix_cache is not None:
            out.update(self.prefix_cache.stats())
        return out
