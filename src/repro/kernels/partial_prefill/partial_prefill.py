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
# One program per (slot, KV head): the query heads that share a KV head
# are folded into the program's rows, so each KV block is read once per
# KV head.  A program loops over only the fused steps its slot's rows can
# attend to (a scalar-prefetch count beside the block table), DMAing each
# step's ``fuse`` table entries itself, double-buffered: a slot with no
# live step costs its program's set-up alone, where a grid step per
# table entry costs its pipeline bookkeeping whether it works or not.
# The clamped table lookup, the per-sub-block positions and the split-KV
# combine are shared with decode_gqa's paged variant in kernels/paged.py.
# ---------------------------------------------------------------------------

# query rows one program holds at a head width of 128 or less (fewer
# for wider heads): the folded query block, its float32 accumulator and
# its scores must fit VMEM
MAX_ROWS = 1024


def heads_per_program(g: int, C: int, hd: int) -> int:
    """Query heads of one KV head folded into one program: the largest
    divisor of ``g`` whose ``heads * C`` rows stay within ``MAX_ROWS``
    (scaled down for heads wider than 128), and at least one head,
    whatever ``C``."""
    cap = MAX_ROWS * 128 // max(hd, 128)
    return max(h for h in range(1, g + 1) if g % h == 0 and
               (h == 1 or h * C <= cap))


def _pp_paged_kernel(bt_ref, n_ref, per_ref, q_ref, qp_ref, k_hbm, v_hbm,
                     p_hbm, om_ref, ol_ref, oa_ref, k_buf, v_buf, p_buf,
                     sems, m_scr, l_scr, acc_scr, *, fuse: int,
                     max_bps: int, progs: int, kv_of: int, window: int,
                     scale: float):
    slot = pl.program_id(0) // progs
    kvh = pl.program_id(0) % progs // kv_of        # this program's KV head
    start = pl.program_id(1) * per_ref[slot]       # this split's live steps
    stop = jnp.minimum(start + per_ref[slot], n_ref[slot])

    def fetch(step, buf):
        """The DMAs of fused step ``step`` into buffer ``buf``: K, V and
        positions of its ``fuse`` table entries (clamped; the unmapped
        ones are masked by their positions)."""
        copies = []
        for j in range(fuse):
            blk = PG.table_entry(bt_ref, slot, step * fuse + j, max_bps)
            copies += [
                pltpu.make_async_copy(k_hbm.at[blk, kvh], k_buf.at[buf, j],
                                      sems.at[buf, 0]),
                pltpu.make_async_copy(v_hbm.at[blk, kvh], v_buf.at[buf, j],
                                      sems.at[buf, 1]),
                pltpu.make_async_copy(p_hbm.at[blk], p_buf.at[buf, j],
                                      sems.at[buf, 2])]
        return copies

    def body(step, carry):
        buf = step % 2

        @pl.when(step + 1 < stop)
        def _prefetch():
            for c in fetch(step + 1, 1 - buf):
                c.start()

        for c in fetch(step, buf):
            c.wait()
        q = q_ref[0].astype(jnp.float32) * scale   # (R, hd), R = heads * C
        q_pos = qp_ref[0, 0]                       # (R,)
        k = k_buf[buf].reshape(-1, k_buf.shape[-1]).astype(jnp.float32)
        v = v_buf[buf].reshape(-1, v_buf.shape[-1]).astype(jnp.float32)
        bs = k_buf.shape[2]
        kv_pos = PG.join_positions(
            bt_ref, [p_buf[buf, j][:, :bs] for j in range(fuse)], slot,
            step * fuse, max_bps)                  # (1, fuse*bs)
        valid = (kv_pos >= 0) & (q_pos[:, None] >= 0) \
            & (kv_pos <= q_pos[:, None])                  # (R, fuse*bs)
        if window:
            valid &= (q_pos[:, None] - kv_pos) < window

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))
        s = jnp.where(valid, s, NEG_INF)

        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(valid, p, 0.0)
        m_scr[...] = m_new
        l_scr[...] = l_prev * corr + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        return carry

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(start < stop)
    def _walk():
        for c in fetch(start, start % 2):
            c.start()
        jax.lax.fori_loop(start, stop, body, 0)

    om_ref[0, 0] = m_scr[...][None, :]
    ol_ref[0, 0] = l_scr[...][None, :]
    oa_ref[0, 0] = acc_scr[...]


def partial_prefill_attention_paged(q, k_pool, v_pool, q_pos, pos_pool,
                                    block_tables, *, window: int = 0,
                                    block_kv: int | None = None,
                                    kv_splits: int = 1,
                                    interpret: bool | None = None):
    """q: (B, C, nh, hd); k_pool, v_pool: (nb, bs, nkv, hd) shared block
    pool; q_pos: (B, C) int32; pos_pool: (nb, bs) int32; block_tables:
    (B, max_bps) int32 (-1 = unmapped).

    The grid is (slot x head group, split): a head group is
    :func:`heads_per_program` query heads of one KV head, their rows
    stacked into one ``(heads * C, hd)`` block.  Each program walks its
    slot's :func:`repro.kernels.paged.live_steps` (``kv_splits > 1``
    divides them into contiguous runs merged by ``PG.combine_splits``),
    a step being ``fuse = block_kv // bs`` consecutive table entries
    (``block_kv=None`` keeps one-block steps).  Positions are masked per
    row (``pos >= 0``, causality, ``window``), so the count bounds the
    work, never the result.  Returns out (B, C, nh, hd).
    """
    interpret = resolve_interpret(interpret)
    B, C, nh, hd = q.shape
    nb, bs, nkv = k_pool.shape[0], k_pool.shape[1], k_pool.shape[2]
    g = nh // nkv
    heads = heads_per_program(g, C, hd)
    progs = nh // heads                            # programs per slot
    R = heads * C
    max_bps = block_tables.shape[1]
    scale = 1.0 / (hd ** 0.5)
    fuse, splits, _ = PG.fused_layout(max_bps, bs, block_kv, kv_splits)

    qh = jnp.moveaxis(q, 2, 1).reshape(B * progs, R, hd)
    kh = jnp.moveaxis(k_pool, 2, 1)                # (nb, nkv, bs, hd)
    vh = jnp.moveaxis(v_pool, 2, 1)
    qp = jnp.tile(q_pos.astype(jnp.int32), (1, heads)).reshape(B, 1, R)
    # a DMA moves whole 128-lane rows: each block's positions fill one
    pp = jnp.pad(pos_pool.astype(jnp.int32),
                 ((0, 0), (0, -bs % 128)), constant_values=-1)[:, None]
    bt = block_tables.astype(jnp.int32)
    n_live = PG.live_steps(q_pos, max_bps, bs, fuse).astype(jnp.int32)
    per = -(-n_live // splits)                     # live steps per split

    kernel = functools.partial(_pp_paged_kernel, fuse=fuse,
                               max_bps=max_bps, progs=progs,
                               kv_of=g // heads, window=window, scale=scale)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B * progs, splits),
        in_specs=[
            pl.BlockSpec((1, R, hd), lambda p, sp, *_: (p, 0, 0)),
            pl.BlockSpec((1, 1, R), lambda p, sp, *_: (p // progs, 0, 0)),
            hbm, hbm, hbm,
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, R), lambda p, sp, *_: (p, sp, 0, 0)),
            pl.BlockSpec((1, 1, 1, R), lambda p, sp, *_: (p, sp, 0, 0)),
            pl.BlockSpec((1, 1, R, hd), lambda p, sp, *_: (p, sp, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, fuse, bs, hd), kh.dtype),
            pltpu.VMEM((2, fuse, bs, hd), vh.dtype),
            pltpu.VMEM((2, fuse) + pp.shape[1:], jnp.int32),
            pltpu.SemaphoreType.DMA((2, 3)),
            pltpu.VMEM((R,), jnp.float32),
            pltpu.VMEM((R,), jnp.float32),
            pltpu.VMEM((R, hd), jnp.float32),
        ],
    )

    m, l, acc = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B * progs, splits, 1, R), jnp.float32),
            jax.ShapeDtypeStruct((B * progs, splits, 1, R), jnp.float32),
            jax.ShapeDtypeStruct((B * progs, splits, R, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="partial_prefill_paged",
    )(bt, n_live, per, qh, qp, kh, vh, pp)

    out = PG.combine_splits(m[:, :, 0], l[:, :, 0], acc, q.dtype)
    return jnp.moveaxis(out.reshape(B, nh, C, hd), 1, 2)
