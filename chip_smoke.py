"""Chip smoke test: serve the paper's SLM/LLM pair on a TPU.

    python chip_smoke.py [--seed 0]       # one chip
    python chip_smoke.py --chips 4        # the fleet path on four chips

One process drives the same library functions as ``repro.launch.serve``
(``PC.make_device``, ``PC.make_engine``, ``PC.profile_pair``,
``SY.run_synera`` over ``SyneraServer``); it starts no child process.
The draft model is ``synera-slm-160m`` whole; the verifier is
``synera-llm-7b`` at every published width, its depth cut to what fits
beside the draft model and a full KV pool.  Both are bf16 with weights
drawn from ``--seed``; prompts are drawn from the seed too, and nothing
is read from disk.

One chip, in order:

1. refuse to start unless JAX sees a TPU (no CPU or interpret fallback);
2. run each main-path Pallas kernel at the verifier's and the draft
   model's widths and compare it with its ``ref.py`` in float32 under
   ``jax.default_matmul_precision("highest")``;
3. choose the verifier depth from the compiled verify step's
   ``memory_analysis()``;
4. calibrate the offload policy (``profile_pair``), then serve
   ``REQUESTS`` prompts through ``SyneraServer`` on the paged Pallas
   path, and check every request returned ``MAX_NEW`` valid tokens;
5. check that the verify, decode and draft programs hold a Pallas
   kernel (``tpu_custom_call``), so no attention call fell back to XLA.

``--chips 4`` runs only the fleet: four replicas behind a prefix-affinity
``ReplicaRouter``, each replica's weights and pool on its own chip, and
the same requests on one replica; the two ``outputs_sha`` must agree.

Every phase prints a ``phase <name> {json}`` line; a failed check
raises.  The last line of standard output is the JSON object
``{"ok": true, "device": {...}}``, printed only when every phase passed.
Wall seconds are host wall-clock time of one run, not a benchmark.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

S_MAX = 2048          # verifier context per slot
SLOTS = 8             # verifier slots; the pool holds SLOTS * S_MAX tokens
BLOCK = 16            # tokens per KV block
CHUNK = 32            # verify chunk (ModelConfig.max_verify_chunk)
REQUESTS = 8          # prompts served, all at once
MAX_NEW = 32          # new tokens per request
PROMPT_ROWS = 256     # draft-model prompt prefill rows in the kernel check
# Verifier depths tried, deepest first.  A depth fits when the widest
# verify step's memory_analysis() (arguments + temporaries + unaliased
# outputs) plus the draft model and its caches stay within the device's
# bytes_limit.  That count is an upper bound of the peak: it takes the
# widest bucket's temporaries as live, and on one v5e the measured
# peak_bytes_in_use stayed gigabytes below it (PERF.md, section 4).
DEPTHS = range(16, 7, -1)
BUDGET = 0.2          # offload budget (the paper's default operating point)

# Kernel tolerances against the float32 reference.
# Output (bf16): the kernel rounds its output to bf16 (half an ulp is
# 3.9e-3 on [1, 2) and 7.8e-3 on [2, 4)), and the MXU rounds the scaled
# queries and the probabilities to bf16 (2^-9 relative each).  The
# outputs are weighted means of unit normals, so |out| < 4; a masking or
# block-table fault moves them by O(0.1).
OUT_TOL = 2e-2
# Importance (f32 column sums): each query row is a distribution of L1
# mass 1; bf16 rounding of the scores perturbs each probability by about
# 1e-2 relative, so the L1 error per query row stays below 2e-2, while a
# masking fault moves O(1) mass.
IMP_L1_TOL = 2e-2


def hbm(device, key: str) -> int:
    """One of the device's memory counters (``bytes_limit``,
    ``peak_bytes_in_use``)."""
    return device.memory_stats()[key]


def log(phase: str, **fields):
    print(f"phase {phase} " + json.dumps(fields, default=float), flush=True)


def outputs_sha(outputs) -> str:
    """Digest of all token streams (the same one ``serve.py`` prints)."""
    return hashlib.sha256(json.dumps(
        [[int(t) for t in o] for o in outputs]).encode()).hexdigest()[:16]


class CompileLog:
    """Backend compile seconds per jitted program name, and persistent
    cache hits, from JAX's monitoring events."""

    def __init__(self):
        import jax

        self.secs: dict[str, list[float]] = {}
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs.setdefault(kw.get("fun_name", "?"), []).append(duration)

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def summary(self, min_secs: float = 0.5) -> dict:
        """Programs that took ``min_secs`` or more in all, and the total."""
        out = {k: {"n": len(v), "secs": sum(v)}
               for k, v in sorted(self.secs.items()) if sum(v) >= min_secs}
        out["all_programs_secs"] = sum(map(sum, self.secs.values()))
        return out


# ---------------------------------------------------------------------------
# Kernels against their references
# ---------------------------------------------------------------------------

def _positions(rng, B, S, C):
    """Per-slot cache lengths in (C, S]; query rows are each slot's
    last ``nq`` positions (written before they attend), -1 padded."""
    import numpy as np

    lens = rng.integers(C + 1, S + 1, B)
    kp = np.full((B, S), -1, np.int32)
    qp = np.full((B, C), -1, np.int32)
    for b, L in enumerate(lens):
        kp[b, :L] = np.arange(L)
        nq = int(rng.integers(1, C + 1))
        qp[b, :nq] = L - nq + np.arange(nq)
    return lens, qp, kp


def check_kernels(llm, slm, seed: int) -> dict:
    """Max error of every main-path kernel against its reference, on
    random bf16 data at the widths the serving run uses."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from benchmarks.kernel_bench import paged_from_dense
    from repro.kernels.attn_importance.attn_importance import (
        attn_with_importance)
    from repro.kernels.attn_importance.ref import attn_with_importance_ref
    from repro.kernels.decode_gqa.decode_gqa import (decode_attention,
                                                     decode_attention_paged)
    from repro.kernels.decode_gqa.ref import decode_attention_ref
    from repro.kernels.partial_prefill.partial_prefill import (
        partial_prefill_attention, partial_prefill_attention_paged)
    from repro.kernels.partial_prefill.ref import partial_prefill_ref

    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    f32 = jnp.float32
    results = {}

    def data(B, T, nh, nkv, hd):
        ks = jax.random.split(jax.random.fold_in(key, len(results)), 3)
        return (jax.random.normal(ks[0], (B, T, nh, hd), jnp.bfloat16),
                jax.random.normal(ks[1], (B, S_MAX, nkv, hd), jnp.bfloat16),
                jax.random.normal(ks[2], (B, S_MAX, nkv, hd), jnp.bfloat16))

    def record(name, out, ref, rows):
        err = float(jnp.max(jnp.abs(out.astype(f32) - ref) * rows))
        results[name] = {"max_abs_err": err, "tol": OUT_TOL}
        assert err <= OUT_TOL, (name, err)

    def ref(fn, *args, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*[a.astype(f32) if a.dtype == jnp.bfloat16 else a
                        for a in args], **kw)

    # verifier widths: 8 slots over the full pool, verify chunk of 32
    B, nh, nkv, hd = SLOTS, llm.n_heads, llm.n_kv_heads, llm.head_dim
    q, k, v = data(B, CHUNK, nh, nkv, hd)
    lens, qp, kp = _positions(rng, B, S_MAX, CHUNK)
    qp, kp = jnp.asarray(qp), jnp.asarray(kp)
    rows = (qp >= 0)[:, :, None, None]
    dq, dqp = q[:, 0], jnp.asarray(lens - 1, jnp.int32)
    record("decode_gqa", decode_attention(dq, k, v, dqp, kp,
                                          block_kv=llm.attn_block_kv),
           ref(decode_attention_ref, dq, k, v, dqp, kp), 1.0)
    pp_ref = ref(partial_prefill_ref, q, k, v, qp, kp)
    record("partial_prefill", partial_prefill_attention(
        q, k, v, qp, kp, block_kv=llm.attn_block_kv), pp_ref, rows)
    kpool, vpool, ppool, bt = paged_from_dense(rng, lens, BLOCK, k, v, kp)
    dec_ref = ref(decode_attention_ref, dq, k, v, dqp, kp)
    for splits in (1, 2):
        tag = f"block_kv{llm.paged_block_kv}_splits{splits}"
        record(f"decode_gqa_paged_{tag}", decode_attention_paged(
            dq, kpool, vpool, dqp, ppool, bt, block_kv=llm.paged_block_kv,
            kv_splits=splits), dec_ref, 1.0)
        record(f"partial_prefill_paged_{tag}", partial_prefill_attention_paged(
            q, kpool, vpool, qp, ppool, bt, block_kv=llm.paged_block_kv,
            kv_splits=splits), pp_ref, rows)
    del k, v, kpool, vpool

    # draft-model widths: one stream; a prompt prefill, then importance
    nh, nkv, hd = slm.n_heads, slm.n_kv_heads, slm.head_dim
    C = PROMPT_ROWS
    q, k, v = data(1, C, nh, nkv, hd)
    lens, qp, kp = _positions(rng, 1, S_MAX, C)
    qp, kp = jnp.asarray(qp), jnp.asarray(kp)
    record("partial_prefill_draft", partial_prefill_attention(
        q, k, v, qp, kp, block_kv=slm.attn_block_kv),
        ref(partial_prefill_ref, q, k, v, qp, kp),
        (qp >= 0)[:, :, None, None])
    L = int(lens[0])
    for tq in (1, C):
        q_pos = jnp.arange(L - tq, L, dtype=jnp.int32)[None]
        kv_pos = jnp.where(jnp.arange(S_MAX) < L, jnp.arange(S_MAX),
                           -1).astype(jnp.int32)[None]
        out, imp = attn_with_importance(q[:, :tq], k, v, q_pos, kv_pos)
        # the reference takes contiguous positions: give it the valid
        # prefix of the cache only
        o_ref, i_ref = ref(attn_with_importance_ref, q[:, :tq], k[:, :L],
                           v[:, :L], q_offset=L - tq)
        name = f"attn_importance_tq{tq}"
        record(name, out, o_ref, 1.0)
        l1 = float(jnp.max(jnp.abs(imp[..., :L] - i_ref).sum(-1))) / tq
        results[name].update(imp_l1_err_per_row=l1, imp_tol=IMP_L1_TOL)
        assert l1 <= IMP_L1_TOL, (name, l1)
        assert float(jnp.max(jnp.abs(imp[..., L:]))) == 0.0, name
    return results


# ---------------------------------------------------------------------------
# Verifier depth from the compiled step's memory analysis
# ---------------------------------------------------------------------------

def tree_bytes(tree) -> int:
    import jax

    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def verify_memory(llm, n_layers: int) -> dict:
    """Compile the widest verify step at ``n_layers`` and read its
    memory analysis (nothing is allocated)."""
    import jax
    import jax.numpy as jnp

    from repro.models import model as M
    from repro.models.steps import make_cloud_verify_step

    cfg = llm.replace(n_layers=n_layers)
    params = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: M.init_cache(cfg, SLOTS, S_MAX,
                                                cache_impl="paged",
                                                block_size=BLOCK))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)   # noqa: E731
    C = 256   # the widest feed bucket (engine.DEFAULT_FEED_BUCKETS)
    step = jax.jit(make_cloud_verify_step(cfg, with_dists=False),
                   donate_argnums=1)
    lowered = step.lower(params, cache, i32(SLOTS, C), i32(SLOTS, C),
                         i32(SLOTS, C), i32(SLOTS, 8), i32(SLOTS))
    try:
        ma = lowered.compile().memory_analysis()
    except jax.errors.JaxRuntimeError as e:
        # the TPU compiler refuses a program larger than the device
        if "RESOURCE_EXHAUSTED" not in str(e):
            raise
        return dict(layers=n_layers, compile_refused=str(e)[:200])
    return dict(layers=n_layers,
                argument_bytes=ma.argument_size_in_bytes,
                output_bytes=ma.output_size_in_bytes,
                alias_bytes=ma.alias_size_in_bytes,
                temp_bytes=ma.temp_size_in_bytes,
                need_bytes=(ma.argument_size_in_bytes + ma.temp_size_in_bytes
                            + ma.output_size_in_bytes
                            - ma.alias_size_in_bytes))


def cut_verifier(slm, llm, device):
    """``llm`` at the deepest of ``DEPTHS`` whose verify step, plus the
    draft model and its per-stream caches, fit in the device's memory;
    and the bytes counted for it."""
    import jax

    from repro.models import model as M

    draft_bytes = tree_bytes(jax.eval_shape(
        lambda: M.init_params(slm, jax.random.PRNGKey(0))))
    draft_bytes += REQUESTS * tree_bytes(jax.eval_shape(
        lambda: M.init_cache(slm, 1, S_MAX)))
    limit = hbm(device, "bytes_limit")
    for n in DEPTHS:
        mem = verify_memory(llm, n)
        counted = mem.get("need_bytes", limit) + draft_bytes
        fits = "need_bytes" in mem and counted <= limit
        log("depth_candidate", **mem, draft_bytes=draft_bytes,
            bytes_limit=limit, fits=fits)
        if fits:
            cut = llm.replace(n_layers=n)
            log("verifier_cut", model=cut.name, layers=n,
                published_layers=llm.n_layers, d_model=cut.d_model,
                n_heads=cut.n_heads, n_kv_heads=cut.n_kv_heads,
                head_dim=cut.head_dim, d_ff=cut.d_ff, vocab=cut.vocab,
                dtype=cut.dtype, params=cut.param_count(),
                counted_bytes=counted)
            return cut, counted
    raise SystemExit(f"no verifier depth in {list(DEPTHS)} fits the device")


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------

def random_params(cfg, seed: int):
    import jax

    from repro.models import model as M

    return jax.jit(M.init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(seed))


def make_prompts(vocab: int, n: int, seed: int) -> list[list[int]]:
    """``n`` prompts of 192-320 tokens over the whole vocabulary."""
    import numpy as np

    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(1, vocab, int(rng.integers(192, 321)))]
            for _ in range(n)]


def build_device(slm, slm_p, llm_engine, prompts):
    """Calibrate the offload policy on ``llm_engine`` (``profile_pair``)
    and return the draft-model runtime that serves with it."""
    from benchmarks import paper_claims as PC
    from repro.core.offload import OffloadPolicy

    dev0 = PC.make_device(slm, slm_p, s_max=S_MAX)
    # profile_pair reads only the prompts; random weights have no task
    evalset = [(p, None) for p in prompts]
    profile, _ = PC.profile_pair(dev0, llm_engine, evalset, None,
                                 max_new=MAX_NEW)
    pol = OffloadPolicy(c_th=profile.c_th,
                        i_th=profile.i_th_for_budget(BUDGET), mode="both")
    return PC.make_device(slm, slm_p, policy=pol, s_max=S_MAX,
                          alpha=profile.alpha), profile


def check_outputs(outputs, vocab: int):
    """Every request returned ``MAX_NEW`` token ids inside the vocab."""
    assert len(outputs) == REQUESTS, len(outputs)
    for o in outputs:
        assert len(o) == MAX_NEW, len(o)
        assert all(0 <= int(t) < vocab for t in o), o


def program_text(jitted, *args) -> str:
    return jitted.lower(*args).compile().as_text()


def check_programs(dev, eng) -> dict:
    """Lower the verify, decode and draft programs as served and require
    a Pallas kernel in each."""
    import numpy as np

    from repro.models import model as M

    B = eng.max_slots
    C = min(eng.compile_stats["buckets"])
    z = lambda *s: np.zeros(s, np.int32)                 # noqa: E731
    texts = {
        "verify": program_text(eng._step_greedy, eng.params, eng.cache,
                               z(B, C), z(B, C), z(B, C),
                               z(B, eng.verify_rows_max), z(B)),
        "decode": program_text(eng._decode, eng.params, eng.cache,
                               z(B, 1), z(B, 1)),
        "draft": program_text(dev._step, dev.params,
                              M.init_cache(dev.cfg, 1, dev.s_max),
                              z(1, 1), z(1, 1)),
    }
    found = {k: t.count("tpu_custom_call") for k, t in texts.items()}
    assert all(found.values()), found
    return found


def serve_one_chip(slm, llm_full, seed: int, clog: CompileLog):
    import jax

    from benchmarks import paper_claims as PC
    from repro.serving import synergy as SY

    dev0 = jax.devices()[0]
    t = time.time()
    kern = check_kernels(llm_full, slm, seed)
    log("kernels", secs=time.time() - t, **kern)
    llm, counted = cut_verifier(slm, llm_full, dev0)

    t = time.time()
    slm_p = random_params(slm, seed)
    eng = PC.make_engine(llm, random_params(llm, seed + 1), slots=SLOTS,
                         attn_impl="pallas", cache_impl="paged",
                         block_size=BLOCK, s_max=S_MAX)
    prompts = make_prompts(llm.vocab, REQUESTS, seed)
    dev, profile = build_device(slm, slm_p, eng, prompts)
    log("setup", secs=time.time() - t, c_th=profile.c_th,
        alpha=profile.alpha, prompt_tokens=[len(p) for p in prompts])

    t = time.time()
    r = SY.run_synera(dev, eng, prompts, MAX_NEW, concurrency=REQUESTS)
    jax.block_until_ready(eng.cache)
    wall = time.time() - t
    check_outputs(r.outputs, llm.vocab)
    st = r.extras["scheduler"]
    log("serve", requests_completed=st["completed_streams"],
        tokens_emitted=sum(len(o) for o in r.outputs),
        outputs_sha=outputs_sha(r.outputs), host_wall_secs_not_a_benchmark=wall,
        iterations=st["iterations"],
        verify_occupancy=st["mean_verify_occupancy"],
        peak_used_blocks=st["peak_used_blocks"], n_blocks=st["n_blocks"],
        feed_buckets=eng.compile_stats["buckets"],
        cloud_token_frac=r.cloud_token_frac)
    assert st["completed_streams"] == REQUESTS, st["completed_streams"]

    log("programs", tpu_custom_call=check_programs(dev, eng))
    log("memory", peak_bytes_in_use=hbm(dev0, "peak_bytes_in_use"),
        counted_bytes=counted, bytes_limit=hbm(dev0, "bytes_limit"))
    log("compile", cache_hits=clog.cache_hits, programs=clog.summary())


def serve_fleet(slm, llm_full, seed: int, clog: CompileLog, n_chips: int):
    import jax

    from benchmarks import paper_claims as PC
    from repro.serving import synergy as SY

    devices = jax.devices()[:n_chips]
    llm, counted = cut_verifier(slm, llm_full, devices[0])

    t = time.time()
    slm_p = random_params(slm, seed)
    llm_p = random_params(llm, seed + 1)
    engines = [PC.make_engine(llm, llm_p, slots=SLOTS, attn_impl="pallas",
                              cache_impl="paged", block_size=BLOCK,
                              s_max=S_MAX, device=d) for d in devices]
    del llm_p
    placed = [sorted({str(d) for x in jax.tree.leaves((e.params, e.cache))
                      for d in x.devices()}) for e in engines]
    assert all(len(p) == 1 for p in placed), placed
    assert len({p[0] for p in placed}) == n_chips, placed
    prompts = make_prompts(llm.vocab, REQUESTS, seed)
    dev, _ = build_device(slm, slm_p, engines[0], prompts)
    log("setup", secs=time.time() - t, replica_devices=[p[0] for p in placed])

    t = time.time()
    fleet = SY.run_synera_fleet(dev, engines, prompts, MAX_NEW,
                                policy="prefix-affinity",
                                concurrency=REQUESTS)
    wall_fleet = time.time() - t
    t = time.time()
    one = SY.run_synera(dev, engines[0], prompts, MAX_NEW,
                        concurrency=REQUESTS)
    wall_one = time.time() - t
    for r in (fleet, one):
        check_outputs(r.outputs, llm.vocab)
    sha_fleet, sha_one = outputs_sha(fleet.outputs), outputs_sha(one.outputs)
    served = [s["completed_streams"] for s in fleet.extras["replicas"]]
    log("fleet", replicas=n_chips, route_policy="prefix-affinity",
        completed_per_replica=served, outputs_sha_fleet=sha_fleet,
        outputs_sha_one_replica=sha_one,
        host_wall_secs_not_a_benchmark={"fleet": wall_fleet,
                                        "one_replica": wall_one})
    assert sha_fleet == sha_one, (sha_fleet, sha_one)
    assert sum(served) == REQUESTS, served
    log("memory", peak_bytes_in_use=[hbm(d, "peak_bytes_in_use")
                                     for d in devices],
        counted_bytes_device0=counted)
    log("compile", cache_hits=clog.cache_hits, programs=clog.summary())


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="1: the whole smoke test on one chip; 4: only "
                         "the four-replica fleet and its one-replica twin")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights, the prompts and the kernel "
                         "check's data")
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX sees "
                 f"{devices[0].platform}); refusing to run on it")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"TPU devices, JAX sees {len(devices)}")

    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from repro.configs.synera_pair import SYNERA_LLM, SYNERA_SLM
    from repro.launch.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    clog = CompileLog()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    log("device", **device, compile_cache=cache_dir,
        jax=jax.__version__)
    slm = SYNERA_SLM.replace(attn_impl="pallas")
    if args.chips == 1:
        serve_one_chip(slm, SYNERA_LLM, args.seed, clog)
    else:
        serve_fleet(slm, SYNERA_LLM, args.seed, clog, args.chips)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
