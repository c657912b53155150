"""Shared machinery for the block-table (paged) attention kernels.

The paged ``decode_gqa``/``partial_prefill`` variants stream a slot's
KV out of the shared block pool through its block table (vLLM /
PagedAttention layout).  ``decode_gqa`` walks the table as grid steps;
``partial_prefill`` loops over only its slot's live steps inside a
program and issues their DMAs itself.  This module owns the pieces
common to both:

* **Fused-DMA layout** (:func:`fused_layout`): each KV step DMAs
  ``fuse = block_kv // block_size`` consecutive block-table entries —
  ``fuse`` independent ``(bs, hd)`` pool-block descriptors issued
  together — so the sequential KV axis shrinks
  from ``max_bps`` single-block steps to ``ceil(max_bps / fuse)``
  dense-sized transfers and the per-step DMA latency is amortized by
  the fusion factor.

* **Clamped table lookup** (:func:`table_entry`): the one shared
  index-map expression that turns a (possibly unmapped, possibly
  past-the-table) table entry into a safe pool block id for the DMA.
  Unmapped entries are masked wholesale in-kernel; the clamp only
  keeps the descriptor in bounds.

* **Split-KV combine** (:func:`combine_splits`): the flash-decode
  epilogue.  With ``kv_splits > 1`` the sequence axis is cut into
  ``splits`` contiguous runs of table entries, each owned by a
  *parallel* program that writes partial online-softmax state
  ``(m, l, acc)``; the epilogue merges the partials.  At
  ``kv_splits=1`` the merge degenerates to the single-pass
  normalization bit-for-bit (``w = exp(m - m) = 1`` exactly).

* **Live steps** (:func:`live_steps`): the fused steps of a slot's table
  its query rows can attend to, so a kernel walks those and no more.

* **Grid accounting** (:func:`paged_grid_info`): the bench reads the
  fused grid shape from here so the step-count reduction is asserted,
  not eyeballed.
"""
from __future__ import annotations

import jax.numpy as jnp

NEG_INF = -1e30


def table_entry(bt, slot, entry, max_bps: int):
    """Clamped block-table lookup shared by every paged index map.

    ``bt`` is the scalar-prefetch (B, max_bps) block table; ``entry``
    may be unmapped (-1) or — in a ragged final fused step — point past
    the table.  Both clamp to a valid pool block id (entry 0 / the last
    column) purely so the DMA descriptor stays in bounds; the kernel
    masks those sub-blocks out via :func:`subblock_mapped`.
    """
    return jnp.maximum(bt[slot, jnp.minimum(entry, max_bps - 1)], 0)


def subblock_mapped(bt_ref, slot, entry, max_bps: int):
    """In-kernel validity of one fused sub-block's table entry: it must
    lie inside the table AND be mapped.  Replaces the single ``mapped``
    scalar of the unfused kernels with one mask per sub-block."""
    return (entry < max_bps) & (
        bt_ref[slot, jnp.minimum(entry, max_bps - 1)] >= 0)


def fused_positions(bt_ref, kp_refs, slot, base, max_bps: int):
    """Positions of one fused grid step's ``fuse`` sub-blocks as one
    (1, fuse * bs) int32 row, from their (1, 1, bs) blocks."""
    return join_positions(bt_ref, [r[0] for r in kp_refs], slot, base,
                          max_bps)


def join_positions(bt_ref, rows, slot, base, max_bps: int):
    """Join the (1, bs) position rows of the table entries ``base``,
    ``base + 1``, ... into one (1, len(rows) * bs) int32 row; a sub-block
    whose table entry is unmapped or past the table reads as -1
    (invalid), so the caller's ``pos >= 0`` test masks it whole.  The
    rows are joined along lanes in int32: the TPU compiler accepts that
    join, and not one of boolean masks."""
    return jnp.concatenate(
        [jnp.where(subblock_mapped(bt_ref, slot, base + j, max_bps),
                   r, -1) for j, r in enumerate(rows)], axis=1)


def fused_layout(max_bps: int, block_size: int, block_kv: int | None,
                 kv_splits: int = 1):
    """Resolve the fused/split grid layout for a paged kernel.

    Returns ``(fuse, splits, spb)``:
      * ``fuse``   — table entries DMAd per grid step
                     (``block_kv // block_size``, clamped to [1, max_bps];
                     ``block_kv=None`` keeps the legacy one-block steps)
      * ``splits`` — parallel flash-decode programs over the sequence
                     (requested ``kv_splits`` clamped so every split owns
                     at least one fused step)
      * ``spb``    — sequential fused steps per split

    ``splits * spb * fuse >= max_bps`` always; ragged tails (table
    lengths that are not a multiple of ``fuse`` or ``splits``) are
    handled by per-sub-block masking in the kernel.
    """
    fuse = 1 if block_kv is None else max(1, block_kv // block_size)
    fuse = min(fuse, max_bps)
    n_fused = -(-max_bps // fuse)
    splits = max(1, min(kv_splits, n_fused))
    spb = -(-n_fused // splits)
    return fuse, splits, spb


def live_steps(q_pos, max_bps: int, block_size: int, fuse: int):
    """Fused steps of each slot's table that its query rows can attend
    to, for ``q_pos`` (B, C) (-1 = padding): none for a slot whose rows
    are all padding, the whole table once its stream has wrapped past
    ``s_max = max_bps * block_size`` (position p lives in entry
    ``(p % s_max) // block_size``), else the entries up to that of its
    highest position.  Takes numpy or jax arrays alike, so the host
    counts what the kernel walks."""
    entries = (q_pos.max(axis=1) // block_size + 1).clip(0, max_bps)
    return -(-entries // fuse)


def paged_grid_info(max_bps: int, block_size: int, block_kv: int | None,
                    kv_splits: int = 1) -> dict:
    """Grid accounting for the bench: steps along the KV axis before
    and after fusion, and the resulting fused/split grid."""
    fuse, splits, spb = fused_layout(max_bps, block_size, block_kv,
                                     kv_splits)
    return dict(
        fuse=fuse,
        splits=splits,
        kv_steps=spb,                       # sequential steps per program
        kv_steps_total=splits * spb,        # KV-axis grid steps overall
        kv_steps_unfused=max_bps,           # the pre-fusion baseline
        tokens_per_step=fuse * block_size,
    )


def combine_splits(m, l, acc, out_dtype):
    """Flash-decode reduction over the split axis (axis 1).

    ``m``/``l``: (N, splits, R) float32 partial online-softmax max /
    normalizer; ``acc``: (N, splits, R, hd) float32 unnormalized
    accumulator.  An empty split carries (NEG_INF, 0, 0) and drops out:
    its weight underflows to 0 against any live split, and an all-empty
    row yields 0 exactly like the single-pass kernels (the ``l == 0``
    guard).  At ``splits == 1`` this is bit-identical to the in-kernel
    ``acc / l`` finish (``exp(m - m) = 1`` and the singleton sum are
    exact).
    """
    m_glob = m.max(axis=1)                                   # (N, R)
    w = jnp.exp(m - m_glob[:, None])                         # (N, S, R)
    l_glob = (w * l).sum(axis=1)                             # (N, R)
    acc_glob = (w[..., None] * acc).sum(axis=1)              # (N, R, hd)
    l_glob = jnp.where(l_glob == 0.0, 1.0, l_glob)
    return (acc_glob / l_glob[..., None]).astype(out_dtype)
