"""Plain float32 reference of a dense GQA decoder (Qwen2 / Qwen3 block).

Pre-norm RMSNorm; attention with optional bias on q/k/v (Qwen2) and
optional per-head RMSNorm on q and k (Qwen3); rotary embedding on the
two halves of each head; causal softmax; SwiGLU MLP
``(silu(x Wg) * (x Wi)) Wo``; final RMSNorm; LM head tied to the
embedding.  Written from the published architecture, in jax.numpy at
``Precision.HIGHEST``, one layer per call so that it fits beside the
weights; it imports nothing of the program.

``control=True`` computes every linear map (projections, MLP, LM head)
on float8 (e4m3) operands, weights scaled per output channel and
activations per token, accumulated in float32: the precision step below
the bfloat16 the configurations state.
"""
from __future__ import annotations

import functools
import math
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
Q_CHUNK = 512
PAD_TO = 1024
HEAD_CHUNK = 256


F8_MAX = 448.0                        # largest finite float8_e4m3fn


def _f8(x, axis):
    """x rounded to float8 e4m3 after scaling its largest magnitude
    along ``axis`` to the format's largest value; returned in float32
    with the scale, so that x ~= q * s."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32), s


def linear(x, w, control: bool):
    """x (L, i) float32 @ w (i, o)."""
    w = w.astype(F32)
    if not control:
        return jnp.dot(x, w, precision=HI)
    xq, sx = _f8(x, 1)
    wq, sw = _f8(w, 0)
    return jnp.dot(xq, wq, precision=HI) * sx * sw


def rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def rope(x, pos, theta):
    """x (L, heads, dh); rotate the two halves of each head."""
    half = x.shape[-1] // 2
    inv = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None] * inv
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


@functools.partial(jax.jit, static_argnums=(0,))
def layer(st, p, x):
    """One decoder layer over a whole (padded) sequence x (L, d)."""
    eps, theta, h, kv, dh, bias, qk_norm, control = st
    L, d = x.shape
    pos = jnp.arange(L, dtype=jnp.int32)
    a = p["attn"]
    y = rms(x, p["ln1"]["scale"], eps)
    q = linear(y, a["wq"].reshape(d, h * dh), control).reshape(L, h, dh)
    k = linear(y, a["wk"].reshape(d, kv * dh), control).reshape(L, kv, dh)
    v = linear(y, a["wv"].reshape(d, kv * dh), control).reshape(L, kv, dh)
    if bias:
        q, k, v = (q + a["bq"].astype(F32), k + a["bk"].astype(F32),
                   v + a["bv"].astype(F32))
    if qk_norm:
        q, k = rms(q, a["q_norm"], eps), rms(k, a["k_norm"], eps)
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    g = h // kv
    qg = q.reshape(L // Q_CHUNK, Q_CHUNK, kv, g, dh)
    scale = 1.0 / math.sqrt(dh)

    def attend(args):
        i, qc = args
        s = jnp.einsum("ckgd,tkd->kgct", qc, k, precision=HI) * scale
        qpos = i * Q_CHUNK + jnp.arange(Q_CHUNK)
        s = jnp.where(pos[None, None, None, :] <= qpos[None, None, :, None],
                      s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgct,tkd->ckgd", w, v, precision=HI)

    o = jax.lax.map(attend, (jnp.arange(L // Q_CHUNK), qg))
    o = o.reshape(L, h * dh)
    x = x + linear(o, a["wo"].reshape(h * dh, d), control)
    m = p["mlp"]
    y = rms(x, p["ln2"]["scale"], eps)
    act = jax.nn.silu(linear(y, m["wg"], control)) * linear(y, m["wi"],
                                                             control)
    return x + linear(act, m["wo"], control)


@functools.partial(jax.jit, static_argnums=(0,))
def _head_gaps(control, x, emb, toks, ref_rows):
    """For positions x (n, d) predicting toks (n,): the reference's best
    logit minus the logit of ``toks``.  With ``control``, the token
    scored is the control's own first choice (``ref_rows`` holds the
    reference's final hidden states at the same positions)."""
    ref = jnp.dot(ref_rows, emb.astype(F32).T, precision=HI)
    best = jnp.max(ref, axis=-1)
    if control:
        mine = linear(x, emb.T, True)
        toks = jnp.argmax(mine, axis=-1)
    return best - jnp.take_along_axis(ref, toks[:, None], axis=1)[:, 0]


def _static(cfg: dict, control: bool):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return (float(cfg["rms_norm_eps"]), float(cfg["rope_theta"]), h,
            cfg["num_key_value_heads"], cfg.get("head_dim") or d // h,
            bool(cfg.get("attention_bias", False)),
            bool(cfg.get("qk_norm", False)), bool(control))


def final_hidden(cfg: dict, params, tokens: Sequence[int],
                 control: bool = False):
    L = len(tokens)
    Lp = -(-L // PAD_TO) * PAD_TO
    toks = np.zeros((Lp,), np.int32)
    toks[:L] = tokens
    emb = params["embed"]["tokens"]
    x = jnp.take(emb, jnp.asarray(toks), axis=0).astype(F32)
    st = _static(cfg, control)
    for p in params["layers"]:
        x = layer(st, p, x)
    return rms(x, params["final_norm"]["scale"], float(cfg["rms_norm_eps"]))


def token_gaps(cfg: dict, params, tokens: Sequence[int], start: int,
               control: bool = False) -> np.ndarray:
    """Gap of every token ``tokens[p]`` for p >= start: how far its
    reference logit (given tokens[:p]) lies below the reference's best.
    With ``control`` the token at each position is the one the float8
    control puts first, not ``tokens[p]``."""
    ref = final_hidden(cfg, params, tokens)
    mine = final_hidden(cfg, params, tokens, True) if control else ref
    emb = params["embed"]["tokens"]
    out = []
    for lo in range(start, len(tokens), HEAD_CHUNK):
        hi = min(lo + HEAD_CHUNK, len(tokens))
        n = hi - lo
        idx = np.full((HEAD_CHUNK,), lo - 1, np.int32)
        idx[:n] = np.arange(lo - 1, hi - 1)
        want = np.zeros((HEAD_CHUNK,), np.int32)
        want[:n] = tokens[lo:hi]
        g = _head_gaps(bool(control), mine[idx], emb, jnp.asarray(want),
                       ref[idx])
        out.append(np.asarray(g)[:n])
    return np.concatenate(out) if out else np.zeros((0,), np.float32)
