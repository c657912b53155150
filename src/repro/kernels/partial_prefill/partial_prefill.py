"""Pallas TPU kernel: chunked partial prefill over a cached KV prefix.

This is the cloud-side hot op of Synera's verification-aware scheduler
(§4.5): a fixed-size chunk (Sarathi chunk, default 32) of
[device-accepted uncached tokens + pending-verify draft tokens] attends
over the request's long cached prefix plus itself (causal within the
chunk by absolute positions).

TPU design:
  * the chunk (C <= 32 queries) is VMEM-resident per (batch, head)
    program; the long KV cache streams through VMEM in blocks of
    ``block_kv`` (HBM -> VMEM pipelining via the grid's minormost axis);
  * online softmax (m, l, acc) lives in VMEM scratch carried across the
    sequential KV-block grid steps — the standard TPU flash-decode
    pattern;
  * positions arrive as explicit arrays (the cache is a circular buffer
    with -1 = invalid slots; padded queries carry position -1), so the
    mask logic is identical to the XLA serving path (layers.cache_write).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret
from repro.kernels import paged as PG

NEG_INF = -1e30


def _pp_kernel(q_ref, k_ref, v_ref, qp_ref, kp_ref, o_ref,
               m_scr, l_scr, acc_scr, *, n_kvb: int, window: int,
               scale: float):
    sb = pl.program_id(1)

    @pl.when(sb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32) * scale      # (C, hd)
    k = k_ref[0].astype(jnp.float32)               # (bkv, hd)
    v = v_ref[0].astype(jnp.float32)               # (bkv, hd)
    q_pos = qp_ref[0, 0]                           # (C,) int32
    kv_pos = kp_ref[0, 0]                          # (bkv,) int32

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (C, bkv)
    valid = (kv_pos[None, :] >= 0) & (q_pos[:, None] >= 0) \
        & (kv_pos[None, :] <= q_pos[:, None])
    if window:
        valid &= (q_pos[:, None] - kv_pos[None, :]) < window
    s = jnp.where(valid, s, NEG_INF)

    m_prev, l_prev, acc_prev = m_scr[...], l_scr[...], acc_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    p = jnp.where(valid, p, 0.0)
    l_new = l_prev * corr + jnp.sum(p, axis=1)
    acc_new = acc_prev * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())))

    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc_new

    @pl.when(sb == n_kvb - 1)
    def _finish():
        l = jnp.where(l_new == 0.0, 1.0, l_new)
        o_ref[0] = (acc_new / l[:, None]).astype(o_ref.dtype)


def partial_prefill_attention(q, k, v, q_pos, kv_pos, *, window: int = 0,
                              block_kv: int = 512,
                              interpret: bool | None = None):
    """q: (B, C, nh, hd); k, v: (B, S, nkv, hd); q_pos: (B, C) int32;
    kv_pos: (B, S) int32 (cache slot positions, -1 = invalid).

    ``interpret=None`` auto-detects (compiled on TPU, interpreter
    elsewhere).  Returns out (B, C, nh, hd).
    """
    interpret = resolve_interpret(interpret)
    B, C, nh, hd = q.shape
    S, nkv = k.shape[1], k.shape[2]
    g = nh // nkv
    scale = 1.0 / (hd ** 0.5)

    bkv = min(block_kv, S)
    n_kvb = pl.cdiv(S, bkv)
    pad = n_kvb * bkv - S
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, ((0, 0), (0, pad)), constant_values=-1)
        S = S + pad

    qh = jnp.moveaxis(q, 2, 1).reshape(B * nh, C, hd)
    kh = jnp.moveaxis(k, 2, 1).reshape(B * nkv, S, hd)
    vh = jnp.moveaxis(v, 2, 1).reshape(B * nkv, S, hd)
    # positions ride as (B, 1, n) so every block's last two dims are
    # (1, n): the TPU tiling accepts a dim equal to the array's
    qp = q_pos.reshape(B, 1, C)
    kp = kv_pos.reshape(B, 1, S)

    kernel = functools.partial(_pp_kernel, n_kvb=n_kvb, window=window,
                               scale=scale)

    out = pl.pallas_call(
        kernel,
        grid=(B * nh, n_kvb),
        in_specs=[
            pl.BlockSpec((1, C, hd), lambda bh, sb: (bh, 0, 0)),
            pl.BlockSpec((1, bkv, hd), lambda bh, sb, g=g: (bh // g, sb, 0)),
            pl.BlockSpec((1, bkv, hd), lambda bh, sb, g=g: (bh // g, sb, 0)),
            pl.BlockSpec((1, 1, C), lambda bh, sb, nh=nh: (bh // nh, 0, 0)),
            pl.BlockSpec((1, 1, bkv),
                         lambda bh, sb, nh=nh: (bh // nh, 0, sb)),
        ],
        out_specs=pl.BlockSpec((1, C, hd), lambda bh, sb: (bh, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * nh, C, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((C,), jnp.float32),
            pltpu.VMEM((C,), jnp.float32),
            pltpu.VMEM((C, hd), jnp.float32),
        ],
        interpret=interpret,
        name="partial_prefill",
    )(qh, kh, vh, qp, kp)

    return jnp.moveaxis(out.reshape(B, nh, C, hd), 1, 2)


# ---------------------------------------------------------------------------
# Block-table (paged) variant: the cached prefix lives in a shared pool
# of fixed-size blocks addressed through per-slot block tables.
#
# Same streaming design as decode_gqa's paged variant (fused multi-block
# DMA + prefetch-friendly arbitrary KV axis + parallel split-KV with a
# jnp combine epilogue); shared machinery lives in kernels/paged.py.
# ---------------------------------------------------------------------------

def _pp_paged_kernel(bt_ref, q_ref, *refs, fuse: int, spb: int,
                     max_bps: int, nh: int, window: int, scale: float):
    k_refs = refs[:fuse]
    v_refs = refs[fuse:2 * fuse]
    qp_ref = refs[2 * fuse]
    kp_refs = refs[2 * fuse + 1:3 * fuse + 1]
    om_ref, ol_ref, oa_ref, m_scr, l_scr, acc_scr = refs[3 * fuse + 1:]

    bh = pl.program_id(0)
    sp = pl.program_id(1)
    sb = pl.program_id(2)

    @pl.when(sb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32) * scale       # (C, hd)
    q_pos = qp_ref[0, 0]                           # (C,)
    slot = bh // nh
    base = (sp * spb + sb) * fuse                  # first table entry here

    k = jnp.concatenate([r[0, 0] for r in k_refs],
                        axis=0).astype(jnp.float32)  # (fuse*bs, hd)
    v = jnp.concatenate([r[0, 0] for r in v_refs], axis=0).astype(jnp.float32)
    kv_pos = PG.fused_positions(bt_ref, kp_refs, slot, base, max_bps)
    valid = (kv_pos >= 0) & (q_pos[:, None] >= 0) \
        & (kv_pos <= q_pos[:, None])                      # (C, fuse*bs)
    if window:
        valid &= (q_pos[:, None] - kv_pos) < window

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (C, fuse*bs)
    s = jnp.where(valid, s, NEG_INF)

    m_prev, l_prev, acc_prev = m_scr[...], l_scr[...], acc_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    p = jnp.where(valid, p, 0.0)
    l_new = l_prev * corr + jnp.sum(p, axis=1)
    acc_new = acc_prev * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())))

    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc_new

    @pl.when(sb == spb - 1)
    def _finish():
        om_ref[0, 0] = m_new[None, :]
        ol_ref[0, 0] = l_new[None, :]
        oa_ref[0, 0] = acc_new


def partial_prefill_attention_paged(q, k_pool, v_pool, q_pos, pos_pool,
                                    block_tables, *, window: int = 0,
                                    block_kv: int | None = None,
                                    kv_splits: int = 1,
                                    interpret: bool | None = None):
    """q: (B, C, nh, hd); k_pool, v_pool: (nb, bs, nkv, hd) shared block
    pool; q_pos: (B, C) int32; pos_pool: (nb, bs) int32; block_tables:
    (B, max_bps) int32 (-1 = unmapped).

    Same scalar-prefetch streaming design as ``decode_attention_paged``:
    each grid step DMAs ``fuse = block_kv // bs`` consecutive table
    entries (``block_kv=None`` keeps legacy one-block steps), the KV
    axis is prefetch-pipelined, and ``kv_splits > 1`` parallelizes over
    contiguous runs of the table with a jnp combine epilogue.  Unmapped
    or past-the-table entries clamp for the DMA and are masked per
    sub-block.  Returns out (B, C, nh, hd).
    """
    interpret = resolve_interpret(interpret)
    B, C, nh, hd = q.shape
    nb, bs, nkv = k_pool.shape[0], k_pool.shape[1], k_pool.shape[2]
    g = nh // nkv
    max_bps = block_tables.shape[1]
    scale = 1.0 / (hd ** 0.5)
    fuse, splits, spb = PG.fused_layout(max_bps, bs, block_kv, kv_splits)

    qh = jnp.moveaxis(q, 2, 1).reshape(B * nh, C, hd)
    kh = jnp.moveaxis(k_pool, 2, 1)                # (nb, nkv, bs, hd)
    vh = jnp.moveaxis(v_pool, 2, 1)
    qp = q_pos.reshape(B, 1, C)
    pp = pos_pool.reshape(nb, 1, bs)
    bt = block_tables.astype(jnp.int32)

    kernel = functools.partial(_pp_paged_kernel, fuse=fuse, spb=spb,
                               max_bps=max_bps, nh=nh, window=window,
                               scale=scale)

    def kv_map(j, nh=nh, g=g):
        def m(bh, sp, sb, bt):
            e = (sp * spb + sb) * fuse + j
            return (PG.table_entry(bt, bh // nh, e, max_bps),
                    (bh % nh) // g, 0, 0)
        return m

    def pos_map(j, nh=nh):
        def m(bh, sp, sb, bt):
            e = (sp * spb + sb) * fuse + j
            return (PG.table_entry(bt, bh // nh, e, max_bps), 0, 0)
        return m

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B * nh, splits, spb),
        in_specs=[
            pl.BlockSpec((1, C, hd), lambda bh, sp, sb, bt: (bh, 0, 0)),
            *[pl.BlockSpec((1, 1, bs, hd), kv_map(j)) for j in range(fuse)],
            *[pl.BlockSpec((1, 1, bs, hd), kv_map(j)) for j in range(fuse)],
            pl.BlockSpec((1, 1, C),
                         lambda bh, sp, sb, bt, nh=nh: (bh // nh, 0, 0)),
            *[pl.BlockSpec((1, 1, bs), pos_map(j)) for j in range(fuse)],
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, C),
                         lambda bh, sp, sb, bt: (bh, sp, 0, 0)),
            pl.BlockSpec((1, 1, 1, C),
                         lambda bh, sp, sb, bt: (bh, sp, 0, 0)),
            pl.BlockSpec((1, 1, C, hd),
                         lambda bh, sp, sb, bt: (bh, sp, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((C,), jnp.float32),
            pltpu.VMEM((C,), jnp.float32),
            pltpu.VMEM((C, hd), jnp.float32),
        ],
    )

    m, l, acc = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B * nh, splits, 1, C), jnp.float32),
            jax.ShapeDtypeStruct((B * nh, splits, 1, C), jnp.float32),
            jax.ShapeDtypeStruct((B * nh, splits, C, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="partial_prefill_paged",
    )(bt, qh, *[kh] * fuse, *[vh] * fuse, qp, *[pp] * fuse)

    out = PG.combine_splits(m[:, :, 0], l[:, :, 0], acc, q.dtype)
    return jnp.moveaxis(out.reshape(B, nh, C, hd), 1, 2)
