"""Plain float32 reference of a decoder LM, independent of the program.

The frame (token embedding, final RMSNorm, a tied or untied head, the
loss) and the pieces that the architectures' layers are built from
(`models/<arch>.py`: RMSNorm, rotary embeddings, causal grouped-query
attention, products that can be tapped), written in plain `jax.numpy`
with every contraction at `Precision.HIGHEST`. The training step runs
layer by layer over the architecture's layer groups (`bench.arch`), and in
blocks of rows, so that it fits one chip beside nothing else.

`bits=4` turns it into the control: every matrix product (attention's two
included) takes its operands, and in the backward pass its incoming
gradient, rounded to a symmetric per-tensor int4 grid. That is the next
precision below the FP8 the configurations state.

At full precision the training step also reads, in its backward pass, the
largest magnitude of every tensor that the configured FP8 recipe stores
in e5m2 without saturation (each group's `sites`), and `Overflow` says
from those and the recipe's delayed scaling whether the loss scaler should
drop the step.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench import arch

HI = lax.Precision.HIGHEST


# -- int4 control ----------------------------------------------------------

def quant_int(x, bits: int):
    """Round x to a symmetric per-tensor grid of 2**(bits-1) - 1 levels."""
    top = 2.0 ** (bits - 1) - 1
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, amax / top, 1.0)
    return jnp.clip(jnp.round(x / s), -top, top) * s


def _einsum(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HI,
                      preferred_element_type=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 3))
def _qeinsum(spec, a, b, bits):
    return _einsum(spec, quant_int(a, bits), quant_int(b, bits))


def _qeinsum_fwd(spec, a, b, bits):
    qa, qb = quant_int(a, bits), quant_int(b, bits)
    return _einsum(spec, qa, qb), (qa, qb)


def _qeinsum_bwd(spec, bits, res, g):
    qa, qb = res
    g = quant_int(g, bits)
    _, vjp = jax.vjp(lambda x, y: _einsum(spec, x, y), qa, qb)
    return vjp(g)


_qeinsum.defvjp(_qeinsum_fwd, _qeinsum_bwd)


def mm(spec, a, b, bits: Optional[int]):
    return _einsum(spec, a, b) if bits is None else _qeinsum(spec, a, b,
                                                             bits)


# -- taps: the backward tensors that the recipe stores in e5m2 ---------------
#
# Each tap is an identity on the forward pass whose backward pass returns,
# as the cotangent of a zero "token" input, the largest magnitudes of the
# tensors it sees: a projection's incoming error dY, its input gradient dA
# and its weight gradient dW; attention's output error dO, dP = dO V^T (on
# the attended positions) and the softmax gradient dS.

def _amax(x):
    return jnp.max(jnp.abs(x))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def tap_mm(spec, a, b, tok):
    """A product whose backward pass gives `tok` the largest magnitudes
    of (dY, dA, dW)."""
    return _einsum(spec, a, b)


def _tap_mm_fwd(spec, a, b, tok):
    return _einsum(spec, a, b), (a, b)


def _tap_mm_bwd(spec, res, g):
    a, b = res
    _, vjp = jax.vjp(lambda x, y: _einsum(spec, x, y), a, b)
    da, db = vjp(g)
    return da, db, jnp.stack([_amax(g), _amax(da), _amax(db)])


tap_mm.defvjp(_tap_mm_fwd, _tap_mm_bwd)


@jax.custom_vjp
def _tap_attn(sc, v, maskf, tok):
    """softmax(sc) V, with sc (b, k, g, q, t) and v (b, t, k, d)."""
    return _einsum("bkgqt,btkd->bqkgd", jax.nn.softmax(sc, axis=-1), v)


def _tap_attn_fwd(sc, v, maskf, tok):
    p = jax.nn.softmax(sc, axis=-1)
    return _einsum("bkgqt,btkd->bqkgd", p, v), (p, v, maskf)


def _tap_attn_bwd(res, g):
    p, v, maskf = res
    dp = _einsum("bqkgd,btkd->bkgqt", g, v)
    dv = _einsum("bkgqt,bqkgd->btkd", p, g)
    ds = p * (dp - jnp.sum(p * dp, axis=-1, keepdims=True))
    amax = jnp.stack([_amax(g), _amax(dp * maskf), _amax(ds)])
    return ds, dv, jnp.zeros_like(maskf), amax


_tap_attn.defvjp(_tap_attn_fwd, _tap_attn_bwd)


def attn_blocks(s: int, q_block: int) -> int:
    """How many query blocks `attention` splits a sequence of s into."""
    nb = max(1, s // q_block)
    return 1 if s % q_block else nb


class Overflow:
    """The configured recipe's overflow rule, applied to the reference's
    own tensors. Delayed scaling gives each site the dequantization scale
    margin x (largest amax in its history, which outlasts the few steps
    compared) / fmax, or 1 while its history is empty, so a site overflows e5m2 once amax x loss scale passes
    cap = margin x history (fmax at first). The reference's ratio of the
    two, the largest over sites and layers, is `ratio`; over `band[1]` the
    step has to be dropped, under `band[0]` kept, and between them either
    is sound: the program's FP8 tensors and their e5m2-rounded history
    differ from the reference's by that much. An overflowing site's history
    takes growth x cap, and a dropped step halves the loss scale. `names`
    names each amax a step gives (`site.layer`)."""

    def __init__(self, recipe: dict, loss_scale: dict, band, names):
        self.fmax = float(recipe["fmax"])
        self.margin = float(recipe["margin"])
        self.growth = float(recipe["growth"])
        self.scale = float(loss_scale["init"])
        self.backoff = float(loss_scale["backoff"])
        self.floor = float(loss_scale["min"])
        self.band = tuple(float(b) for b in band)
        self.names = list(names)
        self.hist = None
        self.log = []

    def decide(self, amax, program_kept=None) -> bool:
        """Whether the step whose amaxes these are is kept:
        the reference's own verdict outside the band, the program's within
        it (where no program is given, the ratio against 1)."""
        a = np.asarray(amax, np.float64) * self.scale
        cap = (np.full_like(a, self.fmax) if self.hist is None
               else np.where(self.hist > 0, self.margin * self.hist,
                             self.fmax))
        r = a / cap
        worst = float(r.max())
        if worst > self.band[1]:
            verdict, kept = "drop", False
        elif worst < self.band[0]:
            verdict, kept = "keep", True
        else:
            verdict = "either"
            kept = (worst < 1.0) if program_kept is None \
                else bool(program_kept)
        obs = np.where(r >= 1.0, self.growth * cap, a)
        self.hist = obs if self.hist is None else np.maximum(self.hist, obs)
        top = np.argsort(r, axis=None)[::-1][:3]
        self.log.append({"ratio": worst, "verdict": verdict, "kept": kept,
                         "loss_scale": self.scale,
                         "top": [(float(r.flat[k]), self.names[k])
                                 for k in top]})
        if not kept:
            self.scale = max(self.scale * self.backoff, self.floor)
        return kept


# -- the model ---------------------------------------------------------------

def rmsnorm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    """x (B, S, H, D), pos (B, S): rotate the two halves of each head."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[..., None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, q_pos, k_pos, *, bits, q_block: int, tok=None):
    """Causal GQA. q (B, S, H, D); k, v (B, T, Hkv, D); positions give the
    mask. Queries go in blocks, each recomputed in the backward pass, so
    one block's scores are alive at a time. `tok` (blocks, 3) taps each
    block's backward tensors."""
    b, s, h, d = q.shape
    hkv = k.shape[2]
    g = h // hkv
    scale = 1.0 / d ** 0.5

    @jax.checkpoint
    def block(qb, qp, tk):
        qb = qb.reshape(b, -1, hkv, g, d)
        sc = mm("bqkgd,btkd->bkgqt", qb, k, bits) * scale
        mask = k_pos[:, None, None, None, :] <= qp[:, None, None, :, None]
        sc = jnp.where(mask, sc, -1e30)
        if tk is None:
            p = jax.nn.softmax(sc, axis=-1)
            o = mm("bkgqt,btkd->bqkgd", p, v, bits)
        else:
            o = _tap_attn(sc, v, mask.astype(jnp.float32), tk)
        return o.reshape(b, -1, h, d)

    nb = attn_blocks(s, q_block)
    if nb == 1:
        return block(q, q_pos, None if tok is None else tok[0])
    qs = q.reshape(b, nb, q_block, h, d).swapaxes(0, 1)
    ps = q_pos.reshape(b, nb, q_block).swapaxes(0, 1)
    out = lax.map(lambda a: block(*a), (qs, ps, tok))
    return out.swapaxes(0, 1).reshape(b, s, h, d)


def head_matrix(w):
    """(matrix, einsum) of the output head: the untied head (d, V), or the
    tied embedding (V, d) used as its transpose."""
    if "head" in w:
        return w["head"], "nd,dv->nv"
    return w["embed"], "nd,vd->nv"


def layer_params(wg: dict, i: int) -> dict:
    """Layer i's leaves of a group's sub-tree (no layer axis)."""
    return {n: x[i] for n, x in wg.items()}


def adam(o, p, mu, nu, g, count):
    """One Adam update (bias-corrected) of one leaf; numpy or jax arrays."""
    mu = o["b1"] * mu + (1 - o["b1"]) * g
    nu = o["b2"] * nu + (1 - o["b2"]) * g * g
    mh = mu / (1 - o["b1"] ** count)
    nh = nu / (1 - o["b2"] ** count)
    return p - o["lr"] * mh / ((nh ** 0.5) + o["eps"]), mu, nu


# -- training: loss and gradients, layer by layer ----------------------------

class Trainer:
    """Three (or more) Adam steps of the reference on one chip's memory.

    The layers are the architecture's groups, one after the other. The
    forward pass keeps each layer's input; the backward pass takes one
    layer's vector-Jacobian product at a time (recomputing inside it) and
    moves that layer's gradient to the host, so no whole gradient tree is
    ever alive on the chip; once the loss scaler's verdict is in, each
    layer is updated from there.
    The loss and its gradient with respect to the final hidden state go in
    blocks of rows, so no (tokens x vocab) logit array is either. Adam's
    moments of the embedding, the head and the final norm stay on the
    host, where their update runs in numpy."""

    def __init__(self, m: dict, opt: dict, *, bits=None, rows: int = 512,
                 q_block: int = 512):
        a = arch.load(m)
        self.z = z = a.dims(m)
        self.groups = a.groups(z)
        self.opt = opt
        self.rows = rows
        self.q_block = q_block
        self.tapped = bits is None
        self._layer, self._layer_vjp, self._tap_amax = {}, {}, {}
        self.site_names, first = [], 0
        for g in self.groups:
            self._jit_group(g, bits)
            self.site_names += [f"{t}.{first + i}" for i in range(g.depth)
                                for t in g.sites]
            first += g.depth
        self._embed = jax.jit(lambda e, t: e[t])
        self._head = jax.jit(self._head_block_fn(), static_argnums=(0,),
                             donate_argnums=(8,))
        self._adam_layer = jax.jit(
            lambda P, MU, NU, g, i, count: tuple(
                a.at[i].set(b) for a, b in zip(
                    (P, MU, NU),
                    adam(opt, P[i], MU[i], NU[i], g,
                         count.astype(jnp.float32)))),
            donate_argnums=(0, 1, 2))
        self._embed_grad = jax.jit(
            lambda ge, t, gx: ge.at[t.reshape(-1)].add(
                gx.reshape(-1, gx.shape[-1])), donate_argnums=(0,))

    def _jit_group(self, g, bits):
        """A group's layer, its vector-Jacobian product (with the taps'
        cotangents where tapped, else None) and its taps' gathering."""
        lay = functools.partial(g.block, z=self.z, bits=bits,
                                q_block=self.q_block)
        self._layer[g.name] = jax.jit(lay)
        if self.tapped:
            self._layer_vjp[g.name] = jax.jit(
                lambda p, x, pos, t, gy: jax.vjp(
                    lambda pp, xx, tt: lay(pp, xx, pos, taps=tt),
                    p, x, t)[1](gy))
        else:
            self._layer_vjp[g.name] = jax.jit(
                lambda p, x, pos, t, gy: jax.vjp(
                    lambda pp, xx: lay(pp, xx, pos), p, x)[1](gy) + (None,))
        self._tap_amax[g.name] = jax.jit(g.tap_amax)

    def _head_block_fn(self):
        z = self.z

        def nll(spec, xb, fn, hm, lab, mask):
            y = rmsnorm(xb, fn, z["eps"])
            lg = mm(spec, y, hm, None)          # the head stays 16-bit
            lz = jax.nn.logsumexp(lg, axis=-1)
            gold = jnp.take_along_axis(lg, lab[:, None], -1)[:, 0]
            return jnp.sum((lz - gold) * mask)

        def fn(spec, xb, fn_w, hm, lab, mask, denom, gfn, ghm):
            val, vjp = jax.vjp(
                lambda a, b, c: nll(spec, a, b, c, lab, mask), xb, fn_w, hm)
            gx, gf, gh = vjp(jnp.float32(1.0) / denom)
            return val, gx, gfn + gf, ghm + gh
        return fn

    def init_state(self, w):
        groups = {g.name for g in self.groups}

        def moments():
            out = {n: np.zeros(v.shape, np.float32)
                   for n, v in w.items() if n not in groups}
            for g in groups:
                out[g] = {n: jnp.zeros_like(v) for n, v in w[g].items()}
            return out
        return {"w": w, "count": 0, "mu": moments(), "nu": moments()}

    def step(self, st, batch, *, on_grad=None, decide=None):
        """One Adam step on {"tokens", "labels", "loss_mask"}; returns the
        loss. `on_grad(group, name, layer, grad)` sees every gradient of a
        step that is applied (group and layer None for a leaf of the
        frame; the layer counted within its group). `decide(amax)`, given
        the largest magnitudes of the step's backward tensors in the order
        of `site_names`, says whether the step is applied (the loss
        scaler's verdict); without it every step is. The gradients wait on
        the host for the verdict."""
        w = st["w"]
        tok = jnp.asarray(batch["tokens"])
        lab = jnp.asarray(batch["labels"])
        mask = jnp.asarray(batch["loss_mask"], jnp.float32)
        b, s = tok.shape
        pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        xs = [self._embed(w["embed"], tok)]
        for g in self.groups:
            for i in range(g.depth):
                xs.append(self._layer[g.name](layer_params(w[g.name], i),
                                              xs[-1], pos))
        hm, spec = head_matrix(w)
        denom = jnp.maximum(mask.sum(), 1.0)
        xf = xs.pop().reshape(b * s, -1)
        labf, maskf = lab.reshape(-1), mask.reshape(-1)
        loss, gxs = 0.0, []
        gfn, ghm = jnp.zeros_like(w["final_norm"]), jnp.zeros_like(hm)
        for r0 in range(0, b * s, self.rows):
            sl = slice(r0, r0 + self.rows)
            val, gx, gfn, ghm = self._head(spec, xf[sl], w["final_norm"],
                                           hm, labf[sl], maskf[sl], denom,
                                           gfn, ghm)
            loss = loss + val
            gxs.append(gx)
        loss = float(loss / denom)
        del xf
        gx = jnp.concatenate(gxs).reshape(b, s, -1)
        held, amax = {}, []
        for g in reversed(self.groups):
            taps = g.zero_taps(s, self.q_block) if self.tapped else None
            for i in reversed(range(g.depth)):
                gp, gx, gt = self._layer_vjp[g.name](
                    layer_params(w[g.name], i), xs.pop(), pos, taps, gx)
                if gt is not None:
                    amax.append(np.asarray(self._tap_amax[g.name](gt)))
                held[g.name, i] = jax.device_get(gp)
                del gp
        if "head" in w:
            grads = {"head": ghm,
                     "embed": self._embed_grad(jnp.zeros_like(w["embed"]),
                                               tok, gx)}
        else:
            grads = {"embed": self._embed_grad(ghm, tok, gx)}
        del ghm, gx
        grads["final_norm"] = gfn
        grads = {n: np.asarray(g) for n, g in grads.items()}
        if decide is not None and not decide(np.concatenate(amax[::-1])):
            return loss
        st["count"] += 1
        for g in reversed(self.groups):
            for i in reversed(range(g.depth)):
                self._apply_layer(st, g, i, held.pop((g.name, i)), on_grad)
        for n in list(grads):
            g = grads.pop(n)
            if on_grad:
                on_grad(None, n, None, g)
            p = np.asarray(w[n])
            p, st["mu"][n], st["nu"][n] = adam(
                self.opt, p, st["mu"][n], st["nu"][n], g,
                np.float32(st["count"]))
            w[n] = jnp.asarray(p.astype(np.float32))
        return loss

    def _apply_layer(self, st, group, i, gp, on_grad):
        """Adam on the leaves of layer i of a group, from its gradient on
        the host."""
        k = group.name
        L, mu, nu = st["w"][k], st["mu"][k], st["nu"][k]
        count = jnp.int32(st["count"])
        for n in group.leaves:
            g = jnp.asarray(gp[n])
            if on_grad:
                on_grad(k, n, i, g)
            L[n], mu[n], nu[n] = self._adam_layer(L[n], mu[n], nu[n], g,
                                                  jnp.int32(i), count)
