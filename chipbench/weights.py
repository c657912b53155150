"""Seeded weights in the program's parameter layout, made on the device
in one jitted call, in the type they are served in.

Each element is a hash of its index, the leaf and the seed (a murmur3
finalizer), mapped to a uniform draw with the leaf's standard
deviation: no host work, no PRNG state, and the same numbers on every
backend.  The seed enters as data, so one compiled program serves every
seed.
"""
from __future__ import annotations

import math
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax


def _fmix(x):
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def _uniform(shape, salt, seed, dtype, std=1.0, mean=0.0):
    """Uniform draws of standard deviation ``std`` around ``mean``."""
    n = math.prod(shape)
    idx = lax.iota(jnp.uint32, n).reshape(shape)
    x = _fmix(idx ^ _fmix(seed[0] + jnp.uint32(salt * 0x9E3779B9 & 0xFFFFFFFF))
              ^ _fmix(seed[1] + jnp.uint32(salt)))
    u = (x >> 8).astype(jnp.float32) * (1.0 / (1 << 24))      # [0, 1)
    return (mean + (u - 0.5) * (std * math.sqrt(12.0))).astype(dtype)


def layout(cfg) -> dict:
    """Shape, mean and standard deviation of every leaf, in the
    program's layout (``repro.models.model.init_params``)."""
    d, V, L = cfg.d_model, cfg.vocab, cfg.n_layers
    nh, nkv, hd, f = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_ff
    attn = {"wq": ((L, d, nh * hd), 0.0, d ** -0.5),
            "wk": ((L, d, nkv * hd), 0.0, d ** -0.5),
            "wv": ((L, d, nkv * hd), 0.0, d ** -0.5),
            "wo": ((L, nh * hd, d), 0.0, (nh * hd) ** -0.5)}
    if cfg.qkv_bias:
        attn.update(bq=((L, nh * hd), 0.0, 0.1), bk=((L, nkv * hd), 0.0, 0.1),
                    bv=((L, nkv * hd), 0.0, 0.1))
    out = {"embed": ((V, d), 0.0, 1.0),
           "final_norm": ((d,), 1.0, 0.1),
           "layers": {"ln1": ((L, d), 1.0, 0.1), "ln2": ((L, d), 1.0, 0.1),
                      "attn": attn,
                      "mlp": {"w_gate": ((L, d, f), 0.0, d ** -0.5),
                              "w_up": ((L, d, f), 0.0, d ** -0.5),
                              "w_down": ((L, f, d), 0.0, f ** -0.5)}}}
    if not cfg.tie_embeddings:
        out["unembed"] = ((d, V), 0.0, d ** -0.5)
    return out


def _is_spec(x):
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[0], tuple)


def seed_words(seed: int) -> np.ndarray:
    """Any whole seed (beyond 32 bits too) as two uint32 words."""
    s = int(seed) % (1 << 64)
    return np.array([s & 0xFFFFFFFF, s >> 32], np.uint32)


@partial(jax.jit, static_argnums=0)
def _make(cfg, seed):
    leaves, tree = jax.tree.flatten(layout(cfg), is_leaf=_is_spec)
    dt = jnp.dtype(cfg.dtype)
    return jax.tree.unflatten(tree, [
        _uniform(shape, i + 1, seed, dt, std, mean)
        for i, (shape, mean, std) in enumerate(leaves)])


def make_params(cfg, seed: int, device=None):
    """The parameters for ``seed`` on ``device`` (default device when
    None), made by one compiled program."""
    words = seed_words(seed)
    if device is not None:
        words = jax.device_put(words, device)
    return _make(cfg, words)


def param_shapes(cfg):
    return jax.eval_shape(lambda s: _make(cfg, s),
                          jax.ShapeDtypeStruct((2,), jnp.uint32))
