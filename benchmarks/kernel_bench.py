"""Kernel microbenchmarks.

CAVEAT: this container executes Pallas in interpret mode on CPU, so
``us_per_call`` is structural-validation timing, NOT TPU performance.
TPU performance is analyzed from the compiled dry-run (§Roofline); the
numbers here certify correctness (max_err vs oracle) and give relative
interpreter cost.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np


def _time(fn, *args, n=3):
    out = fn(*args)  # warmup/compile
    jax.tree.map(lambda x: x.block_until_ready(), out)
    t0 = time.time()
    for _ in range(n):
        out = fn(*args)
        jax.tree.map(lambda x: x.block_until_ready(), out)
    return (time.time() - t0) / n * 1e6


def kernel_micro():
    from repro.kernels.attn_importance.attn_importance import (
        attn_with_importance)
    from repro.kernels.attn_importance.ref import attn_with_importance_ref
    from repro.kernels.decode_gqa.decode_gqa import decode_attention
    from repro.kernels.decode_gqa.ref import decode_attention_ref
    from repro.kernels.partial_prefill.partial_prefill import (
        partial_prefill_attention)
    from repro.kernels.partial_prefill.ref import partial_prefill_ref
    from repro.kernels.ssd_scan.ssd_scan import ssd_scan
    from repro.kernels.ssd_scan.ref import ssd_scan_ref

    rows = []
    key = jax.random.PRNGKey(0)

    # attn + importance: SLM-scale
    B, T, nh, nkv, hd = 1, 256, 8, 4, 64
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, T, nh, hd))
    k = jax.random.normal(ks[1], (B, T, nkv, hd))
    v = jax.random.normal(ks[2], (B, T, nkv, hd))
    f = jax.jit(lambda q, k, v: attn_with_importance(q, k, v))
    us = _time(f, q, k, v)
    o2, i2 = attn_with_importance_ref(q, k, v)
    o1, i1 = f(q, k, v)
    err = max(float(jnp.abs(o1 - o2).max()), float(jnp.abs(i1 - i2).max()))
    rows.append(dict(name="attn_importance", us_per_call=us, max_err=err,
                     shape=f"B{B}xT{T}xh{nh}/{nkv}xd{hd}"))

    # partial prefill: chunk 32 over 1k cache
    B, C, S = 2, 32, 1024
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, C, nh, hd))
    k = jax.random.normal(ks[1], (B, S, nkv, hd))
    v = jax.random.normal(ks[2], (B, S, nkv, hd))
    kp = np.full((B, S), -1, np.int32)
    kp[:, :500] = np.arange(500)
    qp = np.tile(500 + np.arange(C), (B, 1)).astype(np.int32)
    kp[:, 500:500 + C] = qp
    qp, kp = jnp.asarray(qp), jnp.asarray(kp)
    f = jax.jit(lambda *a: partial_prefill_attention(*a, block_kv=256))
    us = _time(f, q, k, v, qp, kp)
    o1 = f(q, k, v, qp, kp)
    o2 = partial_prefill_ref(q, k, v, qp, kp)
    rows.append(dict(name="partial_prefill", us_per_call=us,
                     max_err=float(jnp.abs(o1 - o2).max()),
                     shape=f"B{B}xC{C}xS{S}"))

    # decode GQA
    q1 = jax.random.normal(ks[0], (B, nh, hd))
    qpos = jnp.full((B,), 520, jnp.int32)
    f = jax.jit(lambda *a: decode_attention(*a, block_kv=256))
    us = _time(f, q1, k, v, qpos, kp)
    o1 = f(q1, k, v, qpos, kp)
    o2 = decode_attention_ref(q1, k, v, qpos, kp)
    rows.append(dict(name="decode_gqa", us_per_call=us,
                     max_err=float(jnp.abs(o1 - o2).max()),
                     shape=f"B{B}xS{S}xh{nh}/{nkv}"))

    # SSD scan
    B, L, H, P, N = 1, 256, 4, 32, 16
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (B, L, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, L, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, L, N)) * 0.5
    Cm = jax.random.normal(ks[4], (B, L, N)) * 0.5
    f = jax.jit(lambda *a: ssd_scan(*a, chunk=64))
    us = _time(f, x, dt, A, Bm, Cm)
    y1, h1 = f(x, dt, A, Bm, Cm)
    y2, h2 = ssd_scan_ref(x, dt, A, Bm, Cm, chunk=64)
    rows.append(dict(name="ssd_scan", us_per_call=us,
                     max_err=float(jnp.abs(y1 - y2).max()),
                     shape=f"B{B}xL{L}xH{H}xP{P}xN{N}"))
    return rows


# ---------------------------------------------------------------------------
# Paged-vs-dense sweep (PR 7: fused DMA + flash-decode split-KV)
# ---------------------------------------------------------------------------

def paged_from_dense(rng, lens, bs, kd, vd, kpos, pad=0):
    """Scatter a dense ``(B, S)`` cache into a block pool.

    The pool has ``B * S // bs + pad`` blocks in random order
    (non-contiguous, like a live allocator).  Slot ``b``'s table maps the
    ``ceil(lens[b] / bs)`` blocks that hold its tokens; every other pool
    block is empty (keys and values 0, positions -1).  ``kpos`` must
    already be -1 past each slot's length.  Returns
    ``(k_pool, v_pool, pos_pool, block_tables)``.
    """
    B, S = kpos.shape
    mbps = S // bs
    nb = B * mbps + pad
    perm = rng.permutation(nb)
    bt = np.full((B, mbps), -1, np.int32)
    for b, L in enumerate(lens):
        n = -(-int(L) // bs)
        bt[b, :n] = perm[b * mbps:b * mbps + n]
    src = np.full(nb, B * mbps, np.int32)          # unmapped: empty block
    mapped = bt >= 0
    src[bt[mapped]] = (np.arange(B)[:, None] * mbps
                       + np.arange(mbps)[None])[mapped]

    def pool(x, fill):
        x = jnp.asarray(x)
        flat = x.reshape((B * mbps, bs) + x.shape[2:])
        flat = jnp.concatenate(
            [flat, jnp.full((1,) + flat.shape[1:], fill, flat.dtype)])
        return flat[src]

    return pool(kd, 0), pool(vd, 0), pool(kpos, -1), jnp.asarray(bt)


def _build_paged(rng, B, bs, S, nkv, hd, tail=7):
    """Random paged pool + block tables + the gathered dense view.

    Streams hold ``S - tail`` tokens so the last block is ragged, and 4
    extra pool blocks stay unmapped.
    """
    L = S - tail
    live = np.arange(S) < L
    kd = np.where(live[None, :, None, None],
                  rng.standard_normal((B, S, nkv, hd)), 0).astype(np.float32)
    vd = np.where(live[None, :, None, None],
                  rng.standard_normal((B, S, nkv, hd)), 0).astype(np.float32)
    kpos = np.tile(np.where(live, np.arange(S), -1).astype(np.int32), (B, 1))
    kp, vp, pos, bt = paged_from_dense(rng, [L] * B, bs, kd, vd, kpos, pad=4)
    J = jnp.asarray
    return dict(k_pool=kp, v_pool=vp, pos_pool=pos, bt=bt,
                kd=J(kd), vd=J(vd), kpos=J(kpos), L=L, mbps=S // bs)


def paged_micro(full: bool = True, n: int = 1):
    """Paged-vs-dense rows: correctness (max_err vs the dense kernel on
    the gathered view), interpreter cost ratio, and the grid-step
    accounting the fused-DMA pass exists for (``step_reduction`` =
    unfused KV-axis steps / fused steps; >= 4x at block_kv=128/bs=16).
    """
    from repro.kernels import paged as PG
    from repro.kernels.decode_gqa.decode_gqa import (
        decode_attention, decode_attention_paged)
    from repro.kernels.partial_prefill.partial_prefill import (
        partial_prefill_attention, partial_prefill_attention_paged)

    rows = []
    B, nh, nkv, hd, C = 1, 4, 2, 64, 32
    rng = np.random.default_rng(11)
    sizes = [(bs, S) for bs in (16, 32)
             for S in ((512, 2048, 8192) if full else (512,))]
    for bs, S in sizes:
        # tail = bs + 7: ragged last block AND an unmapped trailing
        # table entry, so every row exercises both mask paths
        d = _build_paged(rng, B, bs, S, nkv, hd, tail=bs + 7)
        L, mbps = d["L"], d["mbps"]
        # decode: one query at the stream head
        q1 = jnp.asarray(rng.standard_normal((B, nh, hd)), jnp.float32)
        qpos1 = jnp.full((B,), L - 1, jnp.int32)
        # partial prefill: a verify chunk of C tokens ending at the head
        qC = jnp.asarray(rng.standard_normal((B, C, nh, hd)), jnp.float32)
        qposC = jnp.tile(jnp.arange(L - C, L, dtype=jnp.int32), (B, 1))
        fdec = jax.jit(lambda *a: decode_attention(*a, block_kv=256))
        fpp = jax.jit(lambda *a: partial_prefill_attention(*a, block_kv=256))
        dense = {
            "decode": (_time(fdec, q1, d["kd"], d["vd"], qpos1, d["kpos"],
                             n=n),
                       fdec(q1, d["kd"], d["vd"], qpos1, d["kpos"])),
            "partial_prefill": (_time(fpp, qC, d["kd"], d["vd"], qposC,
                                      d["kpos"], n=n),
                                fpp(qC, d["kd"], d["vd"], qposC,
                                    d["kpos"])),
        }
        for blk, sp in ((bs, 1), (128, 1), (128, 4)):
            gi = PG.paged_grid_info(mbps, bs, blk, sp)
            for kind, paged_fn, qa, qp in (
                ("decode", decode_attention_paged, q1, qpos1),
                ("partial_prefill", partial_prefill_attention_paged, qC,
                 qposC),
            ):
                f = jax.jit(lambda *a, _f=paged_fn, _b=blk, _s=sp: _f(
                    *a, block_kv=_b, kv_splits=_s))
                args = (qa, d["k_pool"], d["v_pool"], qp, d["pos_pool"],
                        d["bt"])
                us = _time(f, *args, n=n)
                dus, oref = dense[kind]
                err = float(jnp.abs(f(*args) - oref).max())
                rows.append(dict(
                    name=f"paged_{kind}", block_size=bs, S=S,
                    block_kv=blk, kv_splits=sp, fuse=gi["fuse"],
                    kv_steps=gi["kv_steps_total"],
                    kv_steps_unfused=gi["kv_steps_unfused"],
                    step_reduction=gi["kv_steps_unfused"]
                    / gi["kv_steps_total"],
                    tokens_per_step=gi["tokens_per_step"],
                    us_per_call=us, dense_us_per_call=dus,
                    paged_to_dense_ratio=us / dus, max_err=err,
                    shape=f"B{B}xS{S}xh{nh}/{nkv}xd{hd}"))
    return rows


def paged_e2e_rows(max_new: int = 24, n_prompts: int = 3):
    """Greedy token-stream identity, end to end: a paged+pallas engine
    must emit byte-identical streams to the dense+pallas engine across
    fuse/split settings (the serving-level restatement of max_err=0)."""
    from repro.configs.synera_pair import tiny_pair
    from repro.core.offload import OffloadPolicy
    from repro.models import model as M
    from repro.serving import synergy as SY
    from repro.serving.device import DeviceRuntime
    from repro.serving.engine import CloudEngine

    slm_cfg, llm_cfg = tiny_pair(vocab=64)
    llm_cfg = llm_cfg.replace(attn_impl="pallas")
    slm_p = M.init_params(slm_cfg, jax.random.PRNGKey(0))
    llm_p = M.init_params(llm_cfg, jax.random.PRNGKey(1))
    rng = np.random.default_rng(5)
    prompts = [[int(t) for t in rng.integers(1, 60, size=12)]
               for _ in range(n_prompts)]

    def streams(eng):
        dev = DeviceRuntime(slm_cfg, slm_p, s_max=256, gamma=4, seed=0,
                            policy=OffloadPolicy(mode="all"),
                            use_early_exit=False, use_pi=False)
        r = SY.run_synera(dev, eng, prompts, max_new)
        return [[int(t) for t in o] for o in r.outputs]

    ref = streams(CloudEngine(llm_cfg, llm_p, max_slots=2, s_max=256))
    rows = []
    for blk, sp in ((16, 1), (128, 1), (128, 4)):
        out = streams(CloudEngine(llm_cfg, llm_p, max_slots=2, s_max=256,
                                  cache_impl="paged", block_size=16,
                                  paged_block_kv=blk, kv_splits=sp))
        mism = sum(
            len(a) != len(b) or any(x != y for x, y in zip(a, b))
            for a, b in zip(ref, out))
        rows.append(dict(name="paged_e2e_stream", block_size=16,
                         block_kv=blk, kv_splits=sp, n_streams=len(ref),
                         max_new=max_new, token_mismatches=mism))
    return rows


def paged_main(full: bool = True):
    """Paged-kernel bench: prints the sweep, asserts correctness + the
    fusion win, and (full mode) writes BENCH_paged_kernels.json."""
    import json
    import pathlib
    rows = paged_micro(full=full)
    e2e = paged_e2e_rows() if full else []
    print(json.dumps(rows + e2e, indent=2))
    bad = [r for r in rows if not r["max_err"] < 5e-5]
    if bad:
        raise SystemExit(f"paged kernel error vs dense oracle: {bad}")
    weak = [r for r in rows
            if r["block_kv"] == 128 and r["block_size"] == 16
            and r["step_reduction"] < 4]
    if weak:
        raise SystemExit(f"fused-DMA step reduction below 4x: {weak}")
    bad_e2e = [r for r in e2e if r["token_mismatches"] != 0]
    if bad_e2e:
        raise SystemExit(f"paged e2e streams diverged from dense: "
                         f"{bad_e2e}")
    if full:
        out = pathlib.Path(__file__).parent / "BENCH_paged_kernels.json"
        out.write_text(json.dumps(rows + e2e, indent=2) + "\n")
        print(f"wrote {out}")
    print(f"{len(rows)} paged rows OK"
          + (f", {len(e2e)} e2e rows OK" if e2e else ""))


def main():
    """CI smoke: every kernel must run (interpret mode) and match its
    oracle — a cheap early-warning for Pallas dispatch regressions."""
    import json
    rows = kernel_micro()
    print(json.dumps(rows, indent=2))
    bad = [r for r in rows if not r["max_err"] < 5e-2]
    if bad:
        raise SystemExit(f"kernel error vs oracle too large: {bad}")
    print(f"{len(rows)} kernels OK")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    import sys
    if "--paged" in sys.argv:
        paged_main(full=True)
    elif "--paged-smoke" in sys.argv:
        paged_main(full=False)
    else:
        main()
