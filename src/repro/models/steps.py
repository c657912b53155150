"""Step factories: the jit-able entry points used by the launcher, the
serving engine, and the multi-pod dry-run.

Every factory closes over the static config and returns a pure function
of (params, state/batch) suitable for ``jax.jit(..., in_shardings=...)``.

Cache substrate: the serving steps are layout-agnostic — the cache
pytree they thread through ``model.forward`` is either the dense
``(slots, s_max)`` buffer or the paged block pool + per-slot block
tables (``cfg.cache_impl="paged"``), and attention reads/writes route
through the tables structurally (layers.cache_write_paged /
paged_kv_view / the block-table Pallas kernels).  The engine mutates
only the ``block_tables`` leaves between calls, so the jitted steps
never re-specialize on allocation changes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import model as M


def make_train_step(cfg, optimizer, *, micro_batches: int = 1):
    """(params, opt_state, batch) -> (params, opt_state, metrics).

    micro_batches > 1 enables in-step gradient accumulation (§Perf
    iteration 7): the global batch is scanned in micro-batches so live
    activations shrink by the accumulation factor (94-layer 235B MoE at
    1M tokens needs ~147 GiB/device of activations without it; v5e HBM
    is 16 GiB).  Semantics are identical up to f32 grad-mean order.
    """

    def grad_fn(params, batch):
        return jax.value_and_grad(
            lambda p: M.lm_loss(cfg, p, batch), has_aux=True)(params)

    def train_step(params, opt_state, batch):
        if micro_batches == 1:
            (loss, metrics), grads = grad_fn(params, batch)
        else:
            mbatch = jax.tree.map(
                lambda x: x.reshape((micro_batches,
                                     x.shape[0] // micro_batches)
                                    + x.shape[1:]), batch)

            def body(acc, mb):
                (loss_i, metrics_i), g = grad_fn(params, mb)
                acc = jax.tree.map(
                    lambda a, gg: a + gg.astype(jnp.float32), acc, g)
                return acc, (loss_i, metrics_i)

            acc0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            grads, (losses, metricses) = jax.lax.scan(body, acc0, mbatch)
            grads = jax.tree.map(lambda g: g / micro_batches, grads)
            loss = losses.mean()
            metrics = jax.tree.map(lambda x: x.mean(), metricses)
        params, opt_state, opt_m = optimizer.update(grads, opt_state, params)
        metrics = dict(metrics, loss=loss, **opt_m)
        return params, opt_state, metrics

    return train_step


def make_loss_fn(cfg):
    def loss_fn(params, batch):
        loss, metrics = M.lm_loss(cfg, params, batch)
        return loss, metrics
    return loss_fn


def make_prefill_step(cfg):
    """(params, cache, tokens, aux) -> (logits_last, cache).

    tokens: (B, T).  Fills the KV/SSM cache and returns last-position
    logits (the serving prefill).
    """

    def prefill(params, cache, tokens, aux_inputs=None):
        B, T = tokens.shape
        pos = M.default_positions(B, T)
        logits, cache, _, _ = M.forward(cfg, params, tokens, pos, cache=cache,
                                        aux_inputs=aux_inputs)
        return logits[:, -1], cache

    return prefill


def make_decode_step(cfg, *, window: int = 0):
    """(params, cache, token (B,1), pos (B,1)) -> (logits (B,V), cache).

    One auto-regressive step against the cache; ``window`` > 0 selects
    sliding-window attention over a circular cache (long-context decode).
    """

    def decode(params, cache, token, pos):
        logits, cache, _, _ = M.forward(cfg, params, token, pos, cache=cache,
                                        window=window)
        return logits[:, -1], cache

    return decode


def make_verify_step(cfg, *, window: int = 0):
    """The paper's partial prefill (§4.5): a chunk of `uncached accepted
    tokens + pending-verify draft tokens` is forwarded over a KV-cached
    prefix.  Returns per-position logits for the verifier.

    tokens: (B, C) chunk; pos: (B, C) absolute positions (contiguous,
    starting at each request's cached length).

    This is the *legacy/debug* step: it hands the full (B, C, V) logits
    to the host.  The serving hot path uses
    :func:`make_cloud_verify_step`, whose fused epilogue keeps the
    full-vocab tensor device-resident.
    """

    def verify(params, cache, tokens, pos):
        logits, cache, _, _ = M.forward(cfg, params, tokens, pos, cache=cache,
                                        window=window)
        return logits, cache

    return verify


def fused_verify_epilogue(logits, targets, sel_idx, top_k: int,
                          with_dists: bool = True):
    """Device-resident verification epilogue (the hot-path contract).

    logits: (B, C, V); targets: (B, C) int32 token ids whose probability
    the verifier needs (-1 = no target, e.g. the bonus row);
    sel_idx: (B, R) int32 local row indices of the rows the verifier
    will actually consume (the last gamma+1 rows of each request; -1 =
    unused).  R << C, so every vocab-sized reduction touches only the
    selected rows — the chunk's full (B, C, V) logits are consumed by
    nothing but the row gather.

    Returns ``(token_id, p_target, topk_idx, topk_val)`` — the only
    verification state that ever crosses to the host:

    * ``token_id`` (B, R)   -- selected rows' argmax (greedy verification)
    * ``p_target`` (B, R)   -- softmax probability of the selected rows'
      targets (the stochastic accept test of Leviathan verification),
      exact via logsumexp — no full softmax is materialized
    * ``topk_idx/val`` (B, R, K) -- top-k of the selected rows' softmax:
      the cloud's sampling support, used for the rejection-resample
      residual and the bonus token.  Exact when top_k >= vocab;
      otherwise the cloud samples top-k (the same support-compression
      argument as the §4.2 uplink).

    Greedy verification consumes only ``token_id``; pass
    ``with_dists=False`` to skip the probability work entirely (the
    p/top-k outputs come back as zeros) — the scheduler selects the
    variant per iteration from the batched requests' sampling modes.
    """
    B = logits.shape[0]
    R = sel_idx.shape[1]
    lf = logits.astype(jnp.float32)
    selc = jnp.clip(sel_idx, 0, lf.shape[1] - 1).astype(jnp.int32)
    rows = jnp.take_along_axis(lf, selc[..., None], axis=1)       # (B, R, V)
    token_id = jnp.argmax(rows, axis=-1).astype(jnp.int32)        # (B, R)
    if not with_dists:
        return (token_id, jnp.zeros((B, R), jnp.float32),
                jnp.zeros((B, R, top_k), jnp.int32),
                jnp.zeros((B, R, top_k), jnp.float32))
    tsel = jnp.take_along_axis(targets, selc, axis=1)             # (B, R)
    lse = jax.scipy.special.logsumexp(rows, axis=-1)              # (B, R)
    tgt = jnp.clip(tsel, 0, lf.shape[-1] - 1).astype(jnp.int32)
    p_t = jnp.exp(jnp.take_along_axis(rows, tgt[..., None], axis=-1)[..., 0]
                  - lse)
    p_t = jnp.where((tsel >= 0) & (sel_idx >= 0), p_t, 0.0)
    # top-k on logits == top-k on probs (softmax is monotone)
    tkl, topk_idx = jax.lax.top_k(rows, top_k)
    topk_val = jnp.exp(tkl - lse[..., None])
    return token_id, p_t, topk_idx.astype(jnp.int32), topk_val


def make_cloud_verify_step(cfg, *, window: int = 0, top_k: int = 8,
                           with_dists: bool = True):
    """Fused serving step: partial-prefill forward + on-device
    verification epilogue + last-valid-row gather.

    (params, cache, tokens (B,C), pos (B,C), targets (B,C),
     sel_idx (B,R), last_local (B,)) ->
        ((token_id (B,R), p_target (B,R), topk_idx (B,R,K),
          topk_val (B,R,K), last_row (B,V)), cache)

    ``with_dists=False`` compiles the greedy-only variant (argmax rows,
    no probability work).  ``last_local`` indexes each slot's last valid
    row within the chunk; the gathered full-vocab row backs prefill
    completions (the sampling verifier's pre-draft row) — callers only
    fetch it on prefill iterations, so verify iterations never move a
    vocab-sized tensor to the host.
    """

    def cloud_verify(params, cache, tokens, pos, targets, sel_idx,
                     last_local):
        logits, cache, _, _ = M.forward(cfg, params, tokens, pos, cache=cache,
                                        window=window)
        tok, p_t, tk_i, tk_v = fused_verify_epilogue(
            logits, targets, sel_idx, top_k, with_dists=with_dists)
        last = jnp.take_along_axis(
            logits, last_local[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        return (tok, p_t, tk_i, tk_v, last.astype(jnp.float32)), cache

    return cloud_verify


def make_cloud_decode_step(cfg, *, window: int = 0, top_k: int = 8):
    """Fused decode step: one token per slot, returns only the argmax id
    and the top-k sampling support (never the (B, V) logits).

    (params, cache, token (B,1), pos (B,1)) ->
        ((token_id (B,), topk_idx (B,K), topk_val (B,K)), cache)
    """

    def cloud_decode(params, cache, token, pos):
        logits, cache, _, _ = M.forward(cfg, params, token, pos, cache=cache,
                                        window=window)
        row = logits[:, -1].astype(jnp.float32)
        tok = jnp.argmax(row, axis=-1).astype(jnp.int32)
        # top-k on logits == top-k on probs; normalize the K kept values
        # via logsumexp instead of materializing the full softmax
        tkl, tk_i = jax.lax.top_k(row, top_k)
        lse = jax.scipy.special.logsumexp(row, axis=-1)
        tk_v = jnp.exp(tkl - lse[..., None])
        return (tok, tk_i.astype(jnp.int32), tk_v), cache

    return cloud_decode


def make_device_draft_step(cfg):
    """Device-side SLM forward for a draft chunk: returns logits,
    updated cache, and the paper's importance scores (column sums of the
    attention matrix over the cache).  Importance requires materializing
    the matrix, so the implementation is either the naive path or the
    fused attn_importance Pallas kernel (``attn_impl="pallas"``)."""
    dev_cfg = cfg if cfg.attn_impl == "pallas" else cfg.replace(
        attn_impl="naive")

    def draft(params, cache, tokens, pos):
        logits, cache, imp, _ = M.forward(dev_cfg, params, tokens, pos,
                                          cache=cache, return_importance=True)
        return logits, cache, imp

    return draft
