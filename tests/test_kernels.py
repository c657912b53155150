"""Per-kernel correctness: sweep shapes/dtypes and assert_allclose
against the pure-jnp ref.py oracle (kernels run in interpret mode on
CPU; TPU is the compile target)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.attn_importance.attn_importance import attn_with_importance
from repro.kernels.attn_importance.ref import attn_with_importance_ref
from repro.kernels.decode_gqa.decode_gqa import (decode_attention,
                                                 decode_attention_paged)
from repro.kernels.decode_gqa.ref import decode_attention_ref
from repro.kernels.partial_prefill.partial_prefill import (
    heads_per_program, partial_prefill_attention,
    partial_prefill_attention_paged)
from repro.kernels.partial_prefill.ref import partial_prefill_ref
from repro.kernels import paged as PG
from repro.kernels.ssd_scan.ssd_scan import ssd_scan
from repro.kernels.ssd_scan.ref import ssd_scan_ref, ssd_sequential_ref

TOL = {jnp.float32: dict(atol=2e-5, rtol=2e-5),
       jnp.bfloat16: dict(atol=5e-2, rtol=5e-2)}


@pytest.mark.parametrize("B,T,S,nh,nkv,hd,causal", [
    (2, 64, 64, 4, 2, 32, True),
    (1, 100, 100, 8, 8, 64, True),      # non-divisible T (padding)
    (2, 32, 32, 4, 4, 16, False),
    (1, 16, 16, 8, 1, 32, True),        # MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_attn_importance(B, T, S, nh, nkv, hd, causal, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, T, nh, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, nkv, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, nkv, hd), dtype)
    o1, i1 = attn_with_importance(q, k, v, causal=causal, block_q=32)
    o2, i2 = attn_with_importance_ref(q, k, v, causal=causal)
    tol = TOL[dtype]
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32), **tol)
    np.testing.assert_allclose(np.asarray(i1), np.asarray(i2), atol=1e-3)
    # importance column sums over a causal matrix sum to ~Tq per head
    np.testing.assert_allclose(np.asarray(i1.sum(-1)),
                               np.full((B, nh), float(T)), rtol=1e-3)


def _cache_positions(rng, B, S, C):
    qp = np.zeros((B, C), np.int32)
    kp = np.full((B, S), -1, np.int32)
    for b in range(B):
        L = int(rng.integers(C + 1, S - C))
        kp[b, :L] = np.arange(L)
        nq = int(rng.integers(1, C + 1))
        qp[b, :nq] = L + np.arange(nq)
        qp[b, nq:] = -1
        kp[b, L:L + nq] = L + np.arange(nq)  # write-then-attend semantics
    return jnp.asarray(qp), jnp.asarray(kp)


@pytest.mark.parametrize("B,C,S,nh,nkv,hd,window", [
    (2, 8, 128, 4, 2, 32, 0),
    (1, 32, 100, 8, 8, 64, 0),
    (2, 4, 256, 4, 1, 16, 64),
    (3, 16, 96, 6, 3, 32, 0),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_partial_prefill(B, C, S, nh, nkv, hd, window, dtype):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, C, nh, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, nkv, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, nkv, hd), dtype)
    qp, kp = _cache_positions(np.random.default_rng(0), B, S, C)
    o1 = partial_prefill_attention(q, k, v, qp, kp, window=window,
                                   block_kv=64)
    o2 = partial_prefill_ref(q, k, v, qp, kp, window=window)
    mask = (np.asarray(qp) >= 0)[:, :, None, None]
    tol = TOL[dtype]
    np.testing.assert_allclose(np.asarray(o1, np.float32) * mask,
                               np.asarray(o2, np.float32) * mask, **tol)


@pytest.mark.parametrize("B,S,nh,nkv,hd,window", [
    (2, 128, 8, 2, 32, 0),
    (1, 300, 4, 4, 64, 0),     # non-divisible S
    (3, 256, 8, 1, 16, 64),    # MQA + sliding window
    (2, 64, 16, 4, 32, 0),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_gqa(B, S, nh, nkv, hd, window, dtype):
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (B, nh, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, nkv, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, nkv, hd), dtype)
    rng = np.random.default_rng(3)
    kp = np.full((B, S), -1, np.int32)
    qp = np.zeros(B, np.int32)
    for b in range(B):
        L = int(rng.integers(5, S))
        kp[b, :L] = np.arange(L)
        qp[b] = L - 1
    o1 = decode_attention(q, k, v, jnp.asarray(qp), jnp.asarray(kp),
                          window=window, block_kv=64)
    o2 = decode_attention_ref(q, k, v, jnp.asarray(qp), jnp.asarray(kp),
                              window=window)
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32), **TOL[dtype])


# ---------------------------------------------------------------------------
# Block-table (paged) kernel variants: random block tables over a shared
# pool, dense oracle derived by gathering the pool through the tables.
# ---------------------------------------------------------------------------

def _random_paged_cache(rng, B, nb, bs, mbps, nkv, hd, lens, dtype,
                        shared=0):
    """Random pool + permuted tables backing ``lens[b]``-token slots,
    plus the gathered dense-equivalent view.  A slot longer than
    ``s_max = mbps * bs`` has wrapped: entry ``j`` row ``r`` holds the
    latest position ``p < L`` with ``p % s_max == j * bs + r``, as the
    engine's circular window writes it.  ``shared`` leading entries of
    slot 1 map slot 0's blocks, as prefix sharing does."""
    kp = jax.random.normal(jax.random.PRNGKey(7), (nb, bs, nkv, hd), dtype)
    vp = jax.random.normal(jax.random.PRNGKey(8), (nb, bs, nkv, hd), dtype)
    pos = np.full((nb, bs), -1, np.int32)
    bt = np.full((B, mbps), -1, np.int32)
    free = list(rng.permutation(nb))
    s_max = mbps * bs
    for b, L in enumerate(lens):
        for j in range(min(mbps, -(-L // bs))):
            if b == 1 and j < shared:
                bt[b, j] = bt[0, j]
                continue
            blk = free.pop()
            bt[b, j] = blk
            v = j * bs + np.arange(bs)
            p = v + s_max * ((L - 1 - v) // s_max)
            pos[blk] = np.where(p >= 0, p, -1)
    pos, bt = jnp.asarray(pos), jnp.asarray(bt)
    btc = jnp.where(bt < 0, nb, bt)
    s_max = mbps * bs
    kd = jnp.take(kp, btc, axis=0, mode="fill",
                  fill_value=0).reshape(B, s_max, nkv, hd)
    vd = jnp.take(vp, btc, axis=0, mode="fill",
                  fill_value=0).reshape(B, s_max, nkv, hd)
    posd = jnp.take(pos, btc, axis=0, mode="fill",
                    fill_value=-1).reshape(B, s_max)
    return kp, vp, pos, bt, kd, vd, posd


# (block_kv, kv_splits): unfused single-pass (the legacy layout), fused
# multi-block DMA, and fused + flash-decode split-KV.
PAGED_VARIANTS = [(None, 1), (128, 1), (64, 4)]


@pytest.mark.parametrize("B,nb,bs,mbps,nh,nkv,hd,window", [
    (3, 24, 8, 6, 4, 2, 32, 0),
    (2, 12, 16, 4, 8, 8, 64, 0),
    (2, 40, 8, 8, 4, 1, 16, 24),    # MQA + sliding window
])
@pytest.mark.parametrize("blkv,splits", PAGED_VARIANTS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_gqa_paged(B, nb, bs, mbps, nh, nkv, hd, window, blkv,
                          splits, dtype):
    rng = np.random.default_rng(11)
    lens = [int(rng.integers(2, mbps * bs)) for _ in range(B)]
    kp, vp, pos, bt, kd, vd, posd = _random_paged_cache(
        rng, B, nb, bs, mbps, nkv, hd, lens, dtype)
    q = jax.random.normal(jax.random.PRNGKey(9), (B, nh, hd), dtype)
    qp = jnp.asarray([L - 1 for L in lens], jnp.int32)
    o1 = decode_attention_paged(q, kp, vp, qp, pos, bt, window=window,
                                block_kv=blkv, kv_splits=splits)
    o2 = decode_attention_ref(q, kd, vd, qp, posd, window=window)
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32), **TOL[dtype])


@pytest.mark.parametrize("B,C,nb,bs,mbps,nh,nkv,hd,window", [
    (2, 8, 24, 8, 6, 4, 2, 32, 0),
    (1, 16, 12, 16, 4, 8, 8, 64, 0),
    (2, 4, 40, 8, 8, 4, 1, 16, 24),
])
@pytest.mark.parametrize("blkv,splits", PAGED_VARIANTS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_partial_prefill_paged(B, C, nb, bs, mbps, nh, nkv, hd, window,
                               blkv, splits, dtype):
    rng = np.random.default_rng(13)
    lens = [int(rng.integers(C + 1, mbps * bs)) for _ in range(B)]
    kp, vp, pos, bt, kd, vd, posd = _random_paged_cache(
        rng, B, nb, bs, mbps, nkv, hd, lens, dtype)
    q = jax.random.normal(jax.random.PRNGKey(10), (B, C, nh, hd), dtype)
    # chunk queries are the tail of each slot's sequence (already written
    # to the cache: write-then-attend semantics), ragged via -1 padding
    qp = np.full((B, C), -1, np.int32)
    for b in range(B):
        nq = int(rng.integers(1, C + 1))
        qp[b, :nq] = lens[b] - nq + np.arange(nq)
    qp = jnp.asarray(qp)
    o1 = partial_prefill_attention_paged(q, kp, vp, qp, pos, bt,
                                         window=window, block_kv=blkv,
                                         kv_splits=splits)
    o2 = partial_prefill_ref(q, kd, vd, qp, posd, window=window)
    mask = (np.asarray(qp) >= 0)[:, :, None, None]
    np.testing.assert_allclose(np.asarray(o1, np.float32) * mask,
                               np.asarray(o2, np.float32) * mask,
                               **TOL[dtype])


# Slots the paged partial prefill walks in part or not at all, each
# against the dense oracle on every row, padding included (zeros):
# (B, C, nb, bs, mbps, nh, nkv, hd, lens, rows per slot, shared entries)
WALK_CASES = {
    # slots 1 and 3 carry no valid row: they walk no table entry
    "idle_slots": (4, 8, 40, 8, 8, 4, 2, 32, [40, 23, 9, 60], [5, 0, 3, 0],
                   0),
    # slot 0 wrapped past s_max = 64: its whole table is live
    "wrapped": (2, 8, 24, 8, 8, 4, 2, 32, [150, 30], [8, 2], 0),
    # slot 1's first two entries are slot 0's blocks (prefix sharing)
    "shared_block": (2, 8, 24, 8, 6, 4, 2, 32, [44, 37], [8, 4], 2),
    # 8 heads x 256 rows pass the row cap: two programs per KV head
    "heads_split": (2, 256, 40, 16, 16, 8, 1, 16, [256, 300], [256, 20],
                    0),
    # live ranges of 1-2 entries, under one split's 4 at kv_splits 2
    "short_split": (3, 4, 24, 8, 8, 4, 2, 32, [3, 12, 7], [2, 4, 1], 0),
}


@pytest.mark.parametrize("case", sorted(WALK_CASES))
@pytest.mark.parametrize("blkv,splits", PAGED_VARIANTS + [(None, 2)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_partial_prefill_paged_walk(case, blkv, splits, dtype):
    B, C, nb, bs, mbps, nh, nkv, hd, lens, rows, shared = WALK_CASES[case]
    rng = np.random.default_rng(19)
    kp, vp, pos, bt, kd, vd, posd = _random_paged_cache(
        rng, B, nb, bs, mbps, nkv, hd, lens, dtype, shared=shared)
    q = jax.random.normal(jax.random.PRNGKey(12), (B, C, nh, hd), dtype)
    qp = np.full((B, C), -1, np.int32)
    for b, nq in enumerate(rows):
        qp[b, :nq] = lens[b] - nq + np.arange(nq)
    qp = jnp.asarray(qp)
    if case == "heads_split":
        assert heads_per_program(nh // nkv, C, hd) < nh // nkv
    o1 = partial_prefill_attention_paged(q, kp, vp, qp, pos, bt,
                                         block_kv=blkv, kv_splits=splits)
    o2 = partial_prefill_ref(q, kd, vd, qp, posd)
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32), **TOL[dtype])
    idle = [b for b, nq in enumerate(rows) if nq == 0]
    assert not np.asarray(o1, np.float32)[idle].any()


def test_live_steps_bound_the_walk():
    """Live fused steps per slot: none for padding, up to the highest
    position's entry, the whole table once wrapped; numpy (the host's
    count) and jax (the kernel's) agree."""
    qp = np.array([[-1, -1, -1], [0, 1, -1], [15, 16, 17], [63, 64, 65],
                   [200, 201, 202]], np.int32)      # s_max = 4 * 16 = 64
    host = PG.live_steps(qp, 4, 16, 1)
    assert host.tolist() == [0, 1, 2, 4, 4]
    assert PG.live_steps(qp, 4, 16, 3).tolist() == [0, 1, 1, 2, 2]
    assert np.asarray(PG.live_steps(jnp.asarray(qp), 4, 16, 1)).tolist() \
        == host.tolist()


@pytest.mark.parametrize("kind", ["decode", "partial_prefill"])
def test_paged_split_kv_degenerates(kind):
    """Flash-decode split-KV: kv_splits in {1, 2, 4} agree with each
    other (combine epilogue is order-insensitive up to f32 rounding) and
    kv_splits=1 is the single-pass kernel — its combine is an exact
    no-op, so it matches the unfused default bit-for-bit when fuse=1."""
    rng = np.random.default_rng(17)
    B, nb, bs, mbps, nh, nkv, hd, C = 2, 24, 8, 6, 4, 2, 32, 8
    lens = [int(rng.integers(C + 1, mbps * bs)) for _ in range(B)]
    kp, vp, pos, bt, kd, vd, posd = _random_paged_cache(
        rng, B, nb, bs, mbps, nkv, hd, lens, jnp.float32)
    if kind == "decode":
        q = jax.random.normal(jax.random.PRNGKey(21), (B, nh, hd))
        qp = jnp.asarray([L - 1 for L in lens], jnp.int32)
        run = lambda sp, blkv=None: decode_attention_paged(
            q, kp, vp, qp, pos, bt, block_kv=blkv, kv_splits=sp)
        oracle = decode_attention_ref(q, kd, vd, qp, posd)
    else:
        q = jax.random.normal(jax.random.PRNGKey(22), (B, C, nh, hd))
        qp = jnp.asarray(np.stack([lens[b] - C + np.arange(C)
                                   for b in range(B)]), jnp.int32)
        run = lambda sp, blkv=None: partial_prefill_attention_paged(
            q, kp, vp, qp, pos, bt, block_kv=blkv, kv_splits=sp)
        oracle = partial_prefill_ref(q, kd, vd, qp, posd)
    base = run(1)
    # splits=1 degenerates exactly: same grid walk, no-op combine
    assert np.array_equal(np.asarray(run(1, blkv=bs)), np.asarray(base))
    for sp in (2, 4):
        o = run(sp)
        np.testing.assert_allclose(np.asarray(o), np.asarray(base),
                                   atol=2e-6, rtol=2e-6)
        np.testing.assert_allclose(np.asarray(o), np.asarray(oracle),
                                   **TOL[jnp.float32])


@pytest.mark.parametrize("B,L,H,P,N,chunk,use_h0", [
    (2, 64, 4, 16, 8, 16, False),
    (1, 50, 2, 32, 16, 16, True),     # non-divisible L (padding)
    (2, 128, 8, 8, 4, 32, False),
    (1, 33, 3, 8, 8, 8, True),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan(B, L, H, P, N, chunk, use_h0, dtype):
    ks = jax.random.split(jax.random.PRNGKey(4), 6)
    x = (jax.random.normal(ks[0], (B, L, H, P)) * 0.5).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, L, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = (jax.random.normal(ks[3], (B, L, N)) * 0.5).astype(dtype)
    Cm = (jax.random.normal(ks[4], (B, L, N)) * 0.5).astype(dtype)
    h0 = (jax.random.normal(ks[5], (B, H, P, N)) * 0.2) if use_h0 else None
    y1, h1 = ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, h0=h0)
    y2, h2 = ssd_scan_ref(x, dt, A, Bm, Cm, chunk=chunk, h0=h0)
    tol = TOL[dtype]
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y2, np.float32), **tol)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                               atol=tol["atol"] * 10, rtol=tol["rtol"] * 10)
    # against sequential ground truth (f32 only: bf16 accumulates)
    if dtype == jnp.float32:
        y3, h3 = ssd_sequential_ref(x, dt, A, Bm, Cm, h0=h0)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y3), atol=1e-4,
                                   rtol=1e-4)
