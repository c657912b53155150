"""Operations and bytes the verifier's steps need, from the logical
problem: the rows that carry a token and the context each row attends
over.  Padding rows, unmapped blocks and re-reads count for nothing,
whatever the implementation computes, so a share of a peak or of a
roofline built on these counts cannot exceed 1."""
from __future__ import annotations

import numpy as np


def matmul_flops_per_row(cfg) -> int:
    """Multiply-adds (x2) of every weight matrix one row passes through:
    the layers' projections and MLP, and the output head.  The embedding
    is a lookup and counts nothing."""
    d, L, V = cfg.d_model, cfg.n_layers, cfg.vocab
    nh, nkv, hd, f = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_ff
    per_layer = d * nh * hd + 2 * d * nkv * hd + nh * hd * d + 3 * d * f
    return 2 * (L * per_layer + d * V)


def attention_flops(cfg, context: np.ndarray) -> int:
    """Scores and weighted values for rows with the given causal context
    lengths, in every layer: 2 * 2 * heads * head_dim per (row, key)."""
    ctx = np.asarray(context, np.int64)
    return int(4 * cfg.n_heads * cfg.head_dim * cfg.n_layers * ctx.sum())


def step_flops(cfg, positions: np.ndarray) -> int:
    """Model operations a step needs for the rows it fed: each valid row
    (position >= 0) through every matmul, and attention over positions
    0..p of its stream."""
    p = np.asarray(positions)
    valid = p[p >= 0]
    return matmul_flops_per_row(cfg) * valid.size + attention_flops(
        cfg, valid + 1)


def kernel_cost(cfg, positions: np.ndarray) -> tuple[int, int]:
    """The paged ``partial_prefill`` kernel's logical problem for one
    call of the step (one sub-chunk of rows), summed over the layers:
    ``(flops, bytes)``.  Per layer and slot with valid
    rows: scores and values over each row's causal context; the keys
    and values of that context read once (bf16), their positions
    (int32), and the rows' queries and outputs (bf16)."""
    p = np.asarray(positions)
    L, nh, nkv, hd = cfg.n_layers, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    flops = nbytes = 0
    for row in p:
        v = row[row >= 0]
        if v.size == 0:
            continue
        ctx = int(v.max()) + 1
        flops += 4 * nh * hd * int((v + 1).sum())
        nbytes += ctx * (nkv * hd * 2 * 2 + 4) + v.size * nh * hd * 2 * 2
    return flops * L, nbytes * L


def least_seconds(flops: int, nbytes: int, peak: dict) -> float:
    """The roofline's bound: the larger of compute time and memory time
    at the chip's published peaks."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
