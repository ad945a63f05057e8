"""Plain float32 reference of the dense decoder the configurations describe.

Written from the configuration alone, importing nothing of the program:
RMS norm, rotary positions (half-split, ``rope_theta``), grouped-query causal
attention within each packed document, a gated SiLU MLP, an untied LM head,
next-token cross-entropy within each document, and AdamW with global-norm
clipping and a linear-warmup cosine schedule.  Every matmul runs in float32 at
``HIGHEST`` precision, since a TPU otherwise rounds float32 matmul inputs.

It runs beside the model on one chip: weights are upcast one layer at a time
inside a scan over the stacked layers, attention is computed a block of
queries at a time, and the MLP and the head a block of rows at a time.

``Num(low=True)`` is the control: the same computation with the inputs of
every matmul rounded to float8 (e4m3), the precision below the bfloat16 that
the configurations state.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

F8 = jnp.float8_e4m3fn
HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 128
ROW_BLOCK = 2048


@dataclasses.dataclass(frozen=True)
class Num:
    """How the reference multiplies: float32 at HIGHEST, or (``low``) with
    both inputs rounded to float8 e4m3 and float32 accumulation."""
    low: bool = False

    def dot(self, eq, a, b):
        if self.low:
            a = a.astype(F8).astype(jnp.bfloat16)
            b = b.astype(F8).astype(jnp.bfloat16)
            return jnp.einsum(eq, a, b, preferred_element_type=jnp.float32)
        return jnp.einsum(eq, a.astype(jnp.float32), b.astype(jnp.float32),
                          precision=HIGHEST,
                          preferred_element_type=jnp.float32)


def _rms(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _rope(x, pos, theta):
    """x [S, H, D], pos [S]: rotate the two halves of each head."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _rows(fn, block, *xs):
    """Apply a row-local ``fn`` to ``xs`` [S, ...] a block of rows at a
    time (the largest block that divides S and is at most ``block``)."""
    s = xs[0].shape[0]
    block = math.gcd(s, block)
    if s == block:
        return fn(*xs)
    n = s // block
    out = jax.lax.map(jax.checkpoint(lambda a: fn(*a)),
                      tuple(x.reshape((n, block) + x.shape[1:]) for x in xs))
    return jax.tree.map(lambda y: y.reshape((s,) + y.shape[2:]), out)


def _attention(num, q, k, v, seg):
    """q [S, Hq, D], k/v [S, Hkv, D], seg [S]: causal attention within each
    document, a block of queries at a time."""
    s, hq, d = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    q = (q * d ** -0.5).reshape(s, hkv, g, d)
    block = math.gcd(s, Q_BLOCK)
    keys = jnp.arange(s)

    def one(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block)
        sb = jax.lax.dynamic_slice_in_dim(seg, start, block)
        sc = num.dot("qhgd,khd->hgqk", qb, k)
        rows = start + jnp.arange(block)
        mask = (keys[None, :] <= rows[:, None]) & (seg[None, :] == sb[:, None])
        p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
        return num.dot("hgqk,khd->qhgd", p, v).reshape(block, hq * d)

    out = jax.lax.map(jax.checkpoint(one), jnp.arange(0, s, block))
    return out.reshape(s, hq * d)


def _layer(c, num, x, w, pos, seg):
    """One decoder layer; ``w`` holds this layer's weights (any dtype)."""
    eps = c["rms_norm_eps"]
    s = x.shape[0]
    hq, hkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
    h = _rms(x, w["norm1"], eps)
    q = num.dot("sd,de->se", h, w["mixer"]["wq"]).reshape(s, hq, hd)
    k = num.dot("sd,de->se", h, w["mixer"]["wk"]).reshape(s, hkv, hd)
    v = num.dot("sd,de->se", h, w["mixer"]["wv"]).reshape(s, hkv, hd)
    if c.get("qk_norm"):
        q = _rms(q, w["mixer"]["q_norm"], eps)
        k = _rms(k, w["mixer"]["k_norm"], eps)
    q = _rope(q, pos, c["rope_theta"])
    k = _rope(k, pos, c["rope_theta"])
    o = _attention(num, q, k, v, seg)
    x = x + num.dot("se,ed->sd", o, w["mixer"]["wo"])

    def mlp(xb):
        hb = num.dot("sd,df->sf", _rms(xb, w["norm2"], eps), w["mlp"]["wi"])
        gate, up = jnp.split(hb, 2, axis=-1)
        return xb + num.dot("sf,fd->sd", jax.nn.silu(gate) * up,
                            w["mlp"]["wo"])

    return _rows(mlp, ROW_BLOCK, x)


def hidden(c, num, params, tokens, pos, seg):
    """Final-norm hidden states [S, d] of one row of tokens."""
    x = params["embed"][tokens].astype(jnp.float32)

    def body(x, w):
        return _layer(c, num, x, w, pos, seg), None

    x, _ = jax.lax.scan(jax.checkpoint(body), x, params["blocks"]["sub_0"])
    return _rms(x, params["final_norm"], c["rms_norm_eps"])


def _row_loss(c, num, params, tokens, pos, seg):
    """(sum of next-token cross-entropies within documents, their count)
    for one row."""
    h = hidden(c, num, params, tokens, pos, seg)
    labels = jnp.concatenate([tokens[1:], tokens[:1]])
    keep = jnp.concatenate([(seg[1:] == seg[:-1]) & (seg[1:] >= 0),
                            jnp.zeros((1,), bool)])

    def ce(hb, lb, kb):
        logits = num.dot("sd,dv->sv", hb, params["lm_head"])
        lse = jax.nn.logsumexp(logits, -1)
        gold = jnp.take_along_axis(logits, lb[:, None], -1)[:, 0]
        return jnp.sum(jnp.where(kb, lse - gold, 0.0))

    block = math.gcd(h.shape[0], ROW_BLOCK)
    n = h.shape[0] // block
    sums = jax.lax.map(jax.checkpoint(lambda a: ce(*a)),
                       (h.reshape(n, block, -1), labels.reshape(n, block),
                        keep.reshape(n, block)))
    return jnp.sum(sums), jnp.sum(keep)


def batch_loss(c, num, params, batch):
    """Mean next-token cross-entropy over every kept position of the batch
    (rows [B, S] of tokens, segment ids and per-document positions).  The
    rows are laid end to end as one row, each row's documents keeping ids of
    their own, so no attention or label crosses a row."""
    b, s = batch["tokens"].shape
    seg = batch["segment_ids"]
    seg = jnp.where(seg >= 0, seg + s * jnp.arange(b)[:, None], -1)
    sums, count = _row_loss(c, num, params, batch["tokens"].reshape(-1),
                            batch["positions"].reshape(-1), seg.reshape(-1))
    return sums / jnp.maximum(count, 1)


# ------------------------------------------------------------------ training
def learning_rate(opt: dict, step):
    """Linear warmup to the peak, then cosine down to a tenth of it."""
    peak, warm, total = opt["lr"], opt["warmup_steps"], opt["total_steps"]
    step = jnp.asarray(step, jnp.float32)
    warmup = peak * jnp.minimum(1.0, (step + 1) / max(1, warm))
    frac = jnp.clip((step - warm) / max(1, total - warm), 0.0, 1.0)
    cosine = peak * (0.1 + 0.9 * 0.5 * (1 + jnp.cos(jnp.pi * frac)))
    return jnp.where(step < warm, warmup, cosine)


def leaf_norms(tree) -> Dict[str, jnp.ndarray]:
    """L2 norm of every leaf, one per layer for the stacked layer weights."""
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        x = x.astype(jnp.float32)
        if name.startswith("blocks/"):
            out[name] = jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim))))
        else:
            out[name] = jnp.sqrt(jnp.sum(x * x))[None]
    return out


def make_train_step(c: dict, num: Num):
    """One AdamW step of the reference, as two programs so that the
    gradient and the optimizer's state are never all live with the
    activations: ``grad(params, batch) → (loss, grads)`` and
    ``update(params, m, v, grads, step) → (params, m, v, norms of the
    clipped gradient, per leaf)``."""
    opt = c["optimizer"]

    @jax.jit
    def grad(p, batch):
        return jax.value_and_grad(lambda p_: batch_loss(c, num, p_, batch))(p)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def update(p, m, v, g, step):
        gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
        scale = jnp.minimum(1.0, opt["grad_clip"] / (gnorm + 1e-9))
        g = jax.tree.map(lambda x: x * scale, g)
        t = jnp.asarray(step, jnp.float32) + 1
        m = jax.tree.map(lambda a, b: opt["b1"] * a + (1 - opt["b1"]) * b,
                         m, g)
        v = jax.tree.map(lambda a, b: opt["b2"] * a + (1 - opt["b2"]) * b * b,
                         v, g)
        lr = learning_rate(opt, step)
        c1, c2 = 1 - opt["b1"] ** t, 1 - opt["b2"] ** t
        p = jax.tree.map(
            lambda w, a, b: w - lr * ((a / c1) / (jnp.sqrt(b / c2)
                                                  + opt["eps"])
                                      + opt["weight_decay"] * w), p, m, v)
        return p, m, v, leaf_norms(g)

    return grad, update


def train_readings(c: dict, num: Num, make_params0, batches, steps: int):
    """Run ``steps`` reference AdamW steps from the weights that
    ``make_params0()`` makes (the benchmark's, upcast) on ``batches``.
    Returns (losses, per-leaf norms of the first clipped gradient, per-leaf
    norms of the change of the parameters after the last step), on the
    host."""
    grad, update = make_train_step(c, num)
    p = jax.jit(lambda q: jax.tree.map(lambda x: x.astype(jnp.float32), q))(
        make_params0())
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    losses, g0 = [], None
    for i in range(steps):
        b = {k: jnp.asarray(batches[i][k]) for k in
             ("tokens", "positions", "segment_ids")}
        loss, g = grad(p, b)
        p, m, v, gn = update(p, m, v, g, i)
        losses.append(float(loss))
        if g0 is None:
            g0 = jax.device_get(gn)
    del m, v
    change = jax.device_get(jax.jit(lambda a, b: leaf_norms(
        jax.tree.map(lambda x, y: x - y.astype(jnp.float32), a, b)))(
            p, make_params0()))
    return losses, g0, change


# ------------------------------------------------------------------- serving
def make_serve_stats(c: dict, length: int, control: bool = False):
    """For one row of ``length`` tokens: at each position the f32
    reference's largest logit and its logit of the next token in the row;
    with ``control``, also its logit of the token that the float8 control
    ranks first there."""
    f32, low = Num(False), Num(True)

    def head(num, params, h):
        return _rows(lambda hb: num.dot("sd,dv->sv", hb, params["lm_head"]),
                     512, h)

    @jax.jit
    def stats(params, tokens):
        pos = jnp.arange(length, dtype=jnp.int32)
        seg = jnp.zeros((length,), jnp.int32)
        nxt = jnp.concatenate([tokens[1:], tokens[:1]])
        ref = head(f32, params, hidden(c, f32, params, tokens, pos, seg))
        out = [jnp.max(ref, -1),
               jnp.take_along_axis(ref, nxt[:, None], -1)[:, 0]]
        if control:
            ctl = head(low, params, hidden(c, low, params, tokens, pos, seg))
            first = jnp.argmax(ctl, -1)
            out.append(jnp.take_along_axis(ref, first[:, None], -1)[:, 0])
        return out

    def run(params, tokens: np.ndarray):
        return [np.asarray(x) for x in stats(params, jnp.asarray(tokens))]

    return run
