"""Compile the main-path Pallas kernels for a described TPU v5e.

Interpret mode, which every other kernel test runs in, cannot see the
TPU's tiling rules or its fast-memory limits; the TPU compiler, which is
installed here, can, for a chip that is described and not attached.
Each test compiles one kernel at the widths the paper's pair serves at
(verifier ``synera-llm-7b``: 8 slots, a 32-token verify chunk, 32 heads
of 128, 2048 tokens per slot in 16-token blocks; draft model
``synera-slm-160m``: 12 heads of 64) and requires the kernel in the
compiled program.  Nothing runs, so nothing here is a time.

The topology is described inside a fixture, never while a module is
imported: only one process may load the TPU compiler's library, and
under several test workers the one given this file is that process.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.attn_importance.attn_importance import attn_with_importance
from repro.kernels.decode_gqa.decode_gqa import (decode_attention,
                                                 decode_attention_paged)
from repro.kernels.partial_prefill.partial_prefill import (
    partial_prefill_attention, partial_prefill_attention_paged)

B, C, NH, HD, S, BS = 8, 32, 32, 128, 2048, 16     # verifier
SLM_NH, SLM_HD = 12, 64                             # draft model
NB, MBPS = B * S // BS, S // BS
# (block_kv, kv_splits): one table block per grid step, the serving
# default, and the widest fused DMA (the most VMEM), each fused and split
PAGED = [(None, 1), (None, 2), (128, 1), (128, 2), (512, 1), (512, 2)]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:     # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, name, *shapes):
    """Compile ``fn`` and require the kernel in the program under its
    stable ``name`` (the instruction a device trace's op carries)."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert re.search(rf"%{name}(\.\d+)? = .*custom-call\(", text), name


BF, I32 = jnp.bfloat16, jnp.int32
DENSE_KV = [((B, S, NH, HD), BF), ((B, S, NH, HD), BF)]
POOL = [((NB, BS, NH, HD), BF), ((NB, BS, NH, HD), BF)]


def test_decode_gqa_dense(one_chip):
    _compile(lambda q, k, v, qp, kp: decode_attention(
        q, k, v, qp, kp, block_kv=1024, interpret=False), one_chip,
        "decode_gqa", ((B, NH, HD), BF), *DENSE_KV, ((B,), I32),
        ((B, S), I32))


def test_partial_prefill_dense(one_chip):
    _compile(lambda q, k, v, qp, kp: partial_prefill_attention(
        q, k, v, qp, kp, block_kv=1024, interpret=False), one_chip,
        "partial_prefill", ((B, C, NH, HD), BF), *DENSE_KV, ((B, C), I32),
        ((B, S), I32))


@pytest.mark.parametrize("block_kv,splits", PAGED)
def test_decode_gqa_paged(one_chip, block_kv, splits):
    _compile(lambda q, k, v, qp, pp, bt: decode_attention_paged(
        q, k, v, qp, pp, bt, block_kv=block_kv, kv_splits=splits,
        interpret=False), one_chip, "decode_gqa_paged",
        ((B, NH, HD), BF), *POOL, ((B,), I32), ((NB, BS), I32),
        ((B, MBPS), I32))


@pytest.mark.parametrize("block_kv,splits", PAGED)
def test_partial_prefill_paged(one_chip, block_kv, splits):
    _compile(lambda q, k, v, qp, pp, bt: partial_prefill_attention_paged(
        q, k, v, qp, pp, bt, block_kv=block_kv, kv_splits=splits,
        interpret=False), one_chip, "partial_prefill_paged",
        ((B, C, NH, HD), BF), *POOL, ((B, C), I32), ((NB, BS), I32),
        ((B, MBPS), I32))


@pytest.mark.parametrize("block_kv,splits", PAGED)
@pytest.mark.parametrize("c,nkv", [
    (256, 8),      # the prefill width at 4 query heads per KV head: 1024 rows
    (C, 1),        # MQA: all 32 heads of a verify chunk in one program
    (256, 1),      # MQA at the prefill width: the heads split over programs
])
def test_partial_prefill_paged_folded_heads(one_chip, block_kv, splits, c,
                                            nkv):
    """The paged kernel stacks the query heads of a KV head into one
    program's rows; the widest such block must still fit VMEM."""
    pool = ((NB, BS, nkv, HD), BF)
    _compile(lambda q, k, v, qp, pp, bt: partial_prefill_attention_paged(
        q, k, v, qp, pp, bt, block_kv=block_kv, kv_splits=splits,
        interpret=False), one_chip, "partial_prefill_paged",
        ((B, c, NH, HD), BF), pool, pool, ((B, c), I32), ((NB, BS), I32),
        ((B, MBPS), I32))


@pytest.mark.parametrize("tq", [1, 256])
def test_attn_importance(one_chip, tq):
    """The draft model's fused importance kernel: one decode row, and a
    256-row block."""
    kv = ((1, S, SLM_NH, SLM_HD), BF)
    _compile(lambda q, k, v, qp, kp: attn_with_importance(
        q, k, v, qp, kp, interpret=False), one_chip, "attn_importance",
        ((1, tq, SLM_NH, SLM_HD), BF), kv, kv, ((1, tq), I32), ((1, S), I32))
