"""Pallas TPU kernel: GQA decode attention (one token vs a long KV cache).

Decode is memory-bound: the whole KV cache streams through VMEM once per
step.  The GQA structure is the lever — this kernel processes all ``g``
query heads of one KV group per program, so each K/V block is loaded
from HBM ONCE and reused by the whole group (a g-fold HBM-traffic saving
over the per-q-head layout; cf. EXPERIMENTS.md §Perf decode analysis).

Grid = (batch * kv_heads, kv blocks); online-softmax state for the g
group heads lives in VMEM scratch across the sequential block axis.
Supports the circular sliding-window cache (kv_pos = -1 invalid slots,
``window`` for long-context decode).
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


def _decode_kernel(q_ref, k_ref, v_ref, qp_ref, kp_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, n_kvb: int, window: int,
                   scale: float):
    sb = pl.program_id(1)

    @pl.when(sb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32) * scale      # (g, hd)
    k = k_ref[0].astype(jnp.float32)               # (bkv, hd) loaded once
    v = v_ref[0].astype(jnp.float32)               # (bkv, hd)
    q_pos = qp_ref[0, 0]                           # (1,)
    kv_pos = kp_ref[0, 0]                          # (bkv,)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (g, bkv)
    valid = (kv_pos >= 0) & (kv_pos <= q_pos)
    if window:
        valid &= (q_pos - kv_pos) < window
    s = jnp.where(valid[None, :], s, NEG_INF)

    m_prev, l_prev, acc_prev = m_scr[...], l_scr[...], acc_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    p = jnp.where(valid[None, :], p, 0.0)
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


def decode_attention(q, k, v, q_pos, kv_pos, *, window: int = 0,
                     block_kv: int = 512, interpret: bool | None = None):
    """q: (B, nh, hd) one token per request; k, v: (B, S, nkv, hd);
    q_pos: (B,) int32 absolute position; kv_pos: (B, S) int32.

    ``interpret=None`` auto-detects (compiled on TPU, interpreter
    elsewhere).  Returns out (B, nh, hd).
    """
    interpret = resolve_interpret(interpret)
    B, nh, hd = q.shape
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

    qg = q.reshape(B * nkv, g, hd)
    kh = jnp.moveaxis(k, 2, 1).reshape(B * nkv, S, hd)
    vh = jnp.moveaxis(v, 2, 1).reshape(B * nkv, S, hd)
    # positions ride as (B, 1, n) so every block's last two dims are
    # (1, n): the TPU tiling accepts a dim equal to the array's
    qp = q_pos.reshape(B, 1, 1).astype(jnp.int32)
    kp = kv_pos.reshape(B, 1, S)

    kernel = functools.partial(_decode_kernel, n_kvb=n_kvb, window=window,
                               scale=scale)

    out = pl.pallas_call(
        kernel,
        grid=(B * nkv, n_kvb),
        in_specs=[
            pl.BlockSpec((1, g, hd), lambda bk, sb: (bk, 0, 0)),
            pl.BlockSpec((1, bkv, hd), lambda bk, sb: (bk, sb, 0)),
            pl.BlockSpec((1, bkv, hd), lambda bk, sb: (bk, sb, 0)),
            pl.BlockSpec((1, 1, 1),
                         lambda bk, sb, nkv=nkv: (bk // nkv, 0, 0)),
            pl.BlockSpec((1, 1, bkv),
                         lambda bk, sb, nkv=nkv: (bk // nkv, 0, sb)),
        ],
        out_specs=pl.BlockSpec((1, g, hd), lambda bk, sb: (bk, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * nkv, g, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qg, kh, vh, qp, kp)

    return out.reshape(B, nh, hd)


# ---------------------------------------------------------------------------
# Block-table (paged) variant: the KV cache is a shared pool of
# fixed-size blocks; each slot's sequence is scattered across the pool
# and addressed through its block table (vLLM/PagedAttention layout).
#
# Streaming design (see kernels/paged.py and docs/architecture.md):
#   * fused DMA    -- each grid step issues ``fuse`` pool-block
#     descriptors (consecutive table entries) in one pipeline step, so
#     the KV axis runs ceil(max_bps / fuse) dense-sized transfers
#     instead of max_bps single-block ones;
#   * prefetch     -- the KV axis is marked ``arbitrary`` and every
#     descriptor's index map resolves the *next* step's table entries
#     through the scalar-prefetch table, so Mosaic's pipeline starts
#     step N+1's fused DMA while step N computes (double buffering);
#   * split-KV     -- a ``parallel`` split axis partitions the table
#     into contiguous runs; each split writes partial (m, l, acc) and
#     a jnp epilogue (PG.combine_splits) merges them — flash-decode,
#     so one long context uses splits * B * nkv programs, not B * nkv.
# ---------------------------------------------------------------------------

def _decode_paged_kernel(bt_ref, q_ref, *refs, fuse: int, spb: int,
                         max_bps: int, nkv: int, window: int, scale: float):
    k_refs = refs[:fuse]
    v_refs = refs[fuse:2 * fuse]
    qp_ref = refs[2 * fuse]
    kp_refs = refs[2 * fuse + 1:3 * fuse + 1]
    om_ref, ol_ref, oa_ref, m_scr, l_scr, acc_scr = refs[3 * fuse + 1:]

    bk = pl.program_id(0)
    sp = pl.program_id(1)
    sb = pl.program_id(2)

    @pl.when(sb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32) * scale       # (g, hd)
    q_pos = qp_ref[0]                              # (1, 1)
    slot = bk // nkv
    base = (sp * spb + sb) * fuse                  # first table entry here

    k = jnp.concatenate([r[0, 0] for r in k_refs],
                        axis=0).astype(jnp.float32)  # (fuse*bs, hd)
    v = jnp.concatenate([r[0, 0] for r in v_refs], axis=0).astype(jnp.float32)
    kv_pos = PG.fused_positions(bt_ref, kp_refs, slot, base, max_bps)
    valid = (kv_pos >= 0) & (kv_pos <= q_pos)             # (1, fuse*bs)
    if window:
        valid &= (q_pos - kv_pos) < window

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (g, fuse*bs)
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


def decode_attention_paged(q, k_pool, v_pool, q_pos, pos_pool, block_tables,
                           *, window: int = 0, block_kv: int | None = None,
                           kv_splits: int = 1,
                           interpret: bool | None = None):
    """q: (B, nh, hd); k_pool, v_pool: (nb, bs, nkv, hd) shared block
    pool; q_pos: (B,) int32; pos_pool: (nb, bs) int32 (absolute position
    of each pool row, -1 = invalid); block_tables: (B, max_bps) int32
    pool block ids per slot (-1 = unmapped).

    The block table is a scalar-prefetch operand: each grid step's index
    maps read ``fuse = block_kv // bs`` consecutive table entries and DMA
    exactly those pool blocks — no gathered (B, s_max) copy exists, and
    the KV axis walks ceil(max_bps / fuse) dense-sized fused transfers
    (``block_kv=None`` keeps legacy one-block steps).  ``kv_splits > 1``
    adds a parallel flash-decode split axis over the sequence; partial
    (m, l, acc) outputs are merged by :func:`repro.kernels.paged.
    combine_splits` (bit-identical to single-pass at ``kv_splits=1``).
    Unmapped / past-the-table entries clamp for the DMA and are masked
    per sub-block in the kernel.  Returns out (B, nh, hd).
    """
    interpret = resolve_interpret(interpret)
    B, nh, hd = q.shape
    nb, bs, nkv = k_pool.shape[0], k_pool.shape[1], k_pool.shape[2]
    g = nh // nkv
    max_bps = block_tables.shape[1]
    scale = 1.0 / (hd ** 0.5)
    fuse, splits, spb = PG.fused_layout(max_bps, bs, block_kv, kv_splits)

    qg = q.reshape(B * nkv, g, hd)
    kh = jnp.moveaxis(k_pool, 2, 1)                # (nb, nkv, bs, hd)
    vh = jnp.moveaxis(v_pool, 2, 1)
    qp = q_pos.reshape(B, 1, 1).astype(jnp.int32)
    pp = pos_pool.reshape(nb, 1, bs)
    bt = block_tables.astype(jnp.int32)

    kernel = functools.partial(_decode_paged_kernel, fuse=fuse, spb=spb,
                               max_bps=max_bps, nkv=nkv, window=window,
                               scale=scale)

    def kv_map(j, nkv=nkv):
        def m(bk, sp, sb, bt):
            e = (sp * spb + sb) * fuse + j
            return (PG.table_entry(bt, bk // nkv, e, max_bps),
                    bk % nkv, 0, 0)
        return m

    def pos_map(j, nkv=nkv):
        def m(bk, sp, sb, bt):
            e = (sp * spb + sb) * fuse + j
            return (PG.table_entry(bt, bk // nkv, e, max_bps), 0, 0)
        return m

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B * nkv, splits, spb),
        in_specs=[
            pl.BlockSpec((1, g, hd), lambda bk, sp, sb, bt: (bk, 0, 0)),
            *[pl.BlockSpec((1, 1, bs, hd), kv_map(j)) for j in range(fuse)],
            *[pl.BlockSpec((1, 1, bs, hd), kv_map(j)) for j in range(fuse)],
            pl.BlockSpec((1, 1, 1),
                         lambda bk, sp, sb, bt, nkv=nkv: (bk // nkv, 0, 0)),
            *[pl.BlockSpec((1, 1, bs), pos_map(j)) for j in range(fuse)],
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, g),
                         lambda bk, sp, sb, bt: (bk, sp, 0, 0)),
            pl.BlockSpec((1, 1, 1, g),
                         lambda bk, sp, sb, bt: (bk, sp, 0, 0)),
            pl.BlockSpec((1, 1, g, hd),
                         lambda bk, sp, sb, bt: (bk, sp, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g, hd), jnp.float32),
        ],
    )

    m, l, acc = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B * nkv, splits, 1, g), jnp.float32),
            jax.ShapeDtypeStruct((B * nkv, splits, 1, g), jnp.float32),
            jax.ShapeDtypeStruct((B * nkv, splits, g, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(bt, qg, *[kh] * fuse, *[vh] * fuse, qp, *[pp] * fuse)

    out = PG.combine_splits(m[:, :, 0], l[:, :, 0], acc, q.dtype)
    return out.reshape(B, nh, hd)
