"""Plain reference of a dense decoder with grouped-query attention, RoPE
(rotate-half, HF Llama/Mistral/Qwen2 convention), RMSNorm, a SwiGLU MLP,
optional QKV bias and an untied output head: the published layer
equations in float32 ``jax.numpy`` at ``Precision.HIGHEST``, one whole
sequence at a time, with no cache, paging, batching or kernel.  It
imports nothing of the program and reads the weights in their published
roles (``embed``, per layer ``wq wk wv wo``, ``bq bk bv``, ``ln1 ln2``,
``w_gate w_up w_down``, ``final_norm``, ``unembed``).

It runs layer by layer over the checked sequences, each padded to a
multiple of ``BLOCK`` rows, so only one layer's weights are ever held
in float32.  ``quant="fp8"`` is the control: every matmul operand is
rounded to float8 e4m3 (absmax-scaled per row of activations and per
output column of weights) before the float32 product.
"""
from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
BLOCK = 512                  # rows per padded bucket and per query block
NEG = -1e30


def _q8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, quant):
    """x (..., k) @ w (k, n) in float32."""
    if quant:
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.matmul(x, w, precision=HIGHEST)


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x (L, heads, hd); rotate-half with inv_freq theta^(-2i/hd)."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) * 2 / x.shape[-1])
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


@partial(jax.jit, static_argnames=("dims", "quant"))
def _layer(lp, h, pos, dims, quant):
    nh, nkv, hd, eps, theta = dims
    f32 = lambda a: a.astype(jnp.float32)   # noqa: E731
    L = h.shape[0]
    g = nh // nkv
    at = lp["attn"]
    x = _rms(h, f32(lp["ln1"]), eps)
    q, k, v = (_mm(x, f32(at[w]), quant) for w in ("wq", "wk", "wv"))
    if "bq" in at:
        q, k, v = q + f32(at["bq"]), k + f32(at["bk"]), v + f32(at["bv"])
    q = _rope(q.reshape(L, nh, hd), pos, theta).reshape(L, nkv, g, hd)
    k = _rope(k.reshape(L, nkv, hd), pos, theta)
    v = v.reshape(L, nkv, hd)
    outs = []
    for q0 in range(0, L, BLOCK):
        q1 = q0 + BLOCK
        qb, kb, vb = q[q0:q1], k[:q1], v[:q1]
        if quant:
            qb, kb = _q8(qb, -1), _q8(kb, -1)
        s = jnp.einsum("qkgd,skd->kgqs", qb, kb,
                       precision=HIGHEST) / np.sqrt(hd)
        ok = (pos[None, :q1] <= pos[q0:q1, None]) & (pos[None, :q1] >= 0)
        p = jax.nn.softmax(jnp.where(ok, s, NEG), axis=-1)
        if quant:
            p, vb = _q8(p, -1), _q8(vb, 0)
        outs.append(jnp.einsum("kgqs,skd->qkgd", p, vb, precision=HIGHEST))
    o = jnp.concatenate(outs, 0).reshape(L, nh * hd)
    h = h + _mm(o, f32(at["wo"]), quant)
    x = _rms(h, f32(lp["ln2"]), eps)
    m = lp["mlp"]
    gate = _mm(x, f32(m["w_gate"]), quant)
    up = _mm(x, f32(m["w_up"]), quant)
    return h + _mm(gate * jax.nn.sigmoid(gate) * up, f32(m["w_down"]), quant)


@jax.jit
def _take_layer(layers, i):
    return jax.tree.map(lambda a: lax.dynamic_index_in_dim(a, i, 0, False),
                        layers)


@partial(jax.jit, static_argnames=("eps", "quant"))
def _head(h, rows, final_norm, unembed, eps, quant):
    x = _rms(h[rows], final_norm.astype(jnp.float32), eps)
    return _mm(x, unembed.astype(jnp.float32), quant)


@jax.jit
def _embed(embed, tokens):
    return embed[tokens].astype(jnp.float32)


def logits_at(model: dict, params, streams, devices, quant: str | None = None):
    """For each ``(tokens, served)`` stream, the float32 logits of the
    rows that predicted its served tokens: row ``p - 1`` for a token at
    position ``p``.  Streams are spread over ``devices``."""
    dims = (model["num_attention_heads"], model["num_key_value_heads"],
            model.get("head_dim") or model["hidden_size"]
            // model["num_attention_heads"],
            float(model["rms_norm_eps"]), float(model["rope_theta"]))
    q8 = quant == "fp8"
    n_layers = model["num_hidden_layers"]
    placed = []
    for j, (tokens, served) in enumerate(streams):
        dev = devices[j % len(devices)]
        n = len(tokens)
        Lb = -(-n // BLOCK) * BLOCK
        tok = np.zeros(Lb, np.int32)
        tok[:n] = tokens
        pos = np.full(Lb, -1, np.int32)
        pos[:n] = np.arange(n)
        emb = jax.device_put(params["embed"], dev)
        placed.append([dev, _embed(emb, jax.device_put(tok, dev)),
                       jax.device_put(pos, dev),
                       np.array([p - 1 for p, _ in served], np.int32)])
    for i in range(n_layers):
        lp = _take_layer(params["layers"], np.int32(i))
        per_dev = {d: jax.device_put(lp, d) for d in {p[0] for p in placed}}
        for p in placed:
            p[1] = _layer(per_dev[p[0]], p[1], p[2], dims, q8)
    out = []
    for dev, h, _, rows in placed:
        out.append(_head(h, jax.device_put(rows, dev),
                         jax.device_put(params["final_norm"], dev),
                         jax.device_put(params["unembed"], dev),
                         dims[3], q8))
    return out


def served_gaps(model: dict, params, streams, devices) -> list[float]:
    """Per served token: the reference's best logit minus the logit of
    the token the program served at that position."""
    gaps = []
    for lg, (_, served) in zip(logits_at(model, params, streams, devices),
                               streams):
        lg = np.asarray(lg)
        tok = np.array([t for _, t in served])
        gaps += (lg.max(-1) - lg[np.arange(len(tok)), tok]).tolist()
    return gaps


def control_streams(model: dict, params, streams, devices) -> list:
    """The control in the program's place: the same sequences, with each
    served token replaced by the token that the float8 computation puts
    first at that position."""
    out = []
    for lg, (tokens, served) in zip(
            logits_at(model, params, streams, devices, quant="fp8"), streams):
        top = np.asarray(lg).argmax(-1)
        out.append((tokens, [(p, int(t)) for (p, _), t in zip(served, top)]))
    return out
