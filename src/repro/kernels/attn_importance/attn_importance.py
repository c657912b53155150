"""Pallas TPU kernel: attention with fused importance-score extraction.

Synera's importance score (§3.2) is the column-wise sum of the softmax
attention matrix — a quantity flash attention never materializes.  This
kernel fuses the column-sum accumulation into the attention computation
so the device SLM gets (outputs, importance) in one pass over VMEM.

Design for the TPU memory hierarchy (DESIGN.md §2):
  * the device SLM runs short contexts (S <= a few k), so K/V for one
    (batch, kv-head) are VMEM-resident: K,V = 2 * S * hd * 2B
    (S=2048, hd=64 -> 512 KiB), well under the ~16 MiB VMEM budget;
  * grid = (batch * heads, q blocks); the q-block axis is minormost so
    the importance output block (indexed by batch*head only) is revisited
    and accumulated across q blocks — the standard TPU reduction-grid
    pattern;
  * q/k blocks are MXU-aligned (block_q multiple of 128 lanes via hd
    padding in ops.py).

The full (block_q, S) score tile lives in VMEM (128 x 2048 f32 = 1 MiB),
so softmax is computed exactly per row — no online rescaling needed, and
the column sums are exact, not approximated.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def _attn_imp_kernel(q_ref, k_ref, v_ref, qp_ref, kp_ref, o_ref, imp_ref, *,
                     causal: bool, scale: float):
    tb = pl.program_id(1)

    q = q_ref[0].astype(jnp.float32) * scale            # (block_q, hd)
    k = k_ref[0].astype(jnp.float32)                     # (S, hd)
    v = v_ref[0].astype(jnp.float32)                     # (S, hd)
    q_pos = qp_ref[0, 0]                                 # (block_q,) int32
    kv_pos = kp_ref[0, 0]                                # (S,) int32

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (block_q, S)

    # positions are explicit arrays: -1 marks padded query rows and
    # invalid (circular-cache) KV slots, exactly as in the XLA path
    valid = (kv_pos[None, :] >= 0) & (q_pos[:, None] >= 0)
    if causal:
        valid &= kv_pos[None, :] <= q_pos[:, None]
    s = jnp.where(valid, s, NEG_INF)

    m = jnp.max(s, axis=1, keepdims=True)
    p = jnp.exp(s - m)
    p = jnp.where(valid, p, 0.0)
    l = jnp.sum(p, axis=1, keepdims=True)
    l = jnp.where(l == 0.0, 1.0, l)
    p_norm = p / l                                       # (block_q, S)

    o_ref[0] = jax.lax.dot_general(
        p_norm, v, (((1,), (0,)), ((), ()))).astype(o_ref.dtype)

    contrib = jnp.sum(p_norm, axis=0)                    # (S,) column sums

    @pl.when(tb == 0)
    def _init():
        imp_ref[...] = jnp.zeros_like(imp_ref)

    imp_ref[0] += contrib[None, :].astype(imp_ref.dtype)


def attn_with_importance(q, k, v, q_pos=None, kv_pos=None, *,
                         causal: bool = True, q_offset: int = 0,
                         block_q: int = 128,
                         interpret: bool | None = None):
    """q: (B, Tq, nh, hd); k, v: (B, S, nkv, hd) with nh % nkv == 0.

    ``q_pos`` (B, Tq) / ``kv_pos`` (B, S) are optional explicit position
    arrays (-1 = padded query / invalid cache slot), so the kernel can
    serve the serving path's circular cache from inside a jit.  When
    omitted, contiguous positions starting at the static ``q_offset``
    are assumed (the original interface).

    Returns (out (B, Tq, nh, hd), importance (B, nh, S)) — importance is
    the per-head column sum of the softmax matrix over the Tq query rows.
    """
    interpret = resolve_interpret(interpret)
    B, Tq, nh, hd = q.shape
    S, nkv = k.shape[1], k.shape[2]
    g = nh // nkv
    scale = 1.0 / (hd ** 0.5)

    bq = min(block_q, Tq)
    n_qb = pl.cdiv(Tq, bq)
    pad_q = n_qb * bq - Tq

    if q_pos is None:
        q_pos = q_offset + jnp.broadcast_to(
            jnp.arange(Tq, dtype=jnp.int32)[None], (B, Tq))
    if kv_pos is None:
        kv_pos = jnp.broadcast_to(
            jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    q_pos = q_pos.astype(jnp.int32)
    kv_pos = kv_pos.astype(jnp.int32)

    # (B*nh, Tq, hd) per-head layout
    qh = jnp.moveaxis(q, 2, 1).reshape(B * nh, Tq, hd)
    if pad_q:
        qh = jnp.pad(qh, ((0, 0), (0, pad_q), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pad_q)), constant_values=-1)
    kh = jnp.moveaxis(k, 2, 1).reshape(B * nkv, S, hd)
    vh = jnp.moveaxis(v, 2, 1).reshape(B * nkv, S, hd)
    # positions and importance ride as (·, 1, n) so every block's last
    # two dims are (1, n): the TPU tiling accepts a dim equal to the
    # array's
    qp = q_pos.reshape(B, 1, n_qb * bq)
    kp = kv_pos.reshape(B, 1, S)

    kernel = functools.partial(_attn_imp_kernel, causal=causal, scale=scale)

    out, imp = pl.pallas_call(
        kernel,
        grid=(B * nh, n_qb),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda bh, tb: (bh, tb, 0)),
            pl.BlockSpec((1, S, hd), lambda bh, tb, g=g: (bh // g, 0, 0)),
            pl.BlockSpec((1, S, hd), lambda bh, tb, g=g: (bh // g, 0, 0)),
            pl.BlockSpec((1, 1, bq),
                         lambda bh, tb, nh=nh: (bh // nh, 0, tb)),
            pl.BlockSpec((1, 1, S), lambda bh, tb, nh=nh: (bh // nh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, hd), lambda bh, tb: (bh, tb, 0)),
            pl.BlockSpec((1, 1, S), lambda bh, tb: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * nh, n_qb * bq, hd), q.dtype),
            jax.ShapeDtypeStruct((B * nh, 1, S), jnp.float32),
        ],
        interpret=interpret,
    )(qh, kh, vh, qp, kp)

    out = out[:, :Tq].reshape(B, nh, Tq, hd)
    out = jnp.moveaxis(out, 1, 2)
    imp = imp.reshape(B, nh, S)
    return out, imp
