"""The engine's count of the paged partial-prefill kernel's KV walk
(``attn_kv_steps_grid`` / ``attn_kv_steps_live``), on the benchmark's
tiny engine: ``chipbench/tests/tiny.py`` widths, served as a cell is."""
import numpy as np

from chipbench import cells, weights
from chipbench.tests.tiny import tiny_cell
from repro.serving.engine import CloudEngine


def _tiny_engine():
    model = tiny_cell("mistral-7b", "chat")["model"]
    sv = model["serving"]
    cfg = cells.model_config(model)
    return CloudEngine(cfg, weights.make_params(cfg, 2_718_281_831),
                       max_slots=sv["slots"], s_max=sv["s_max"],
                       cache_impl="paged", block_size=sv["block_size"],
                       pool_blocks=sv["pool_blocks"],
                       feed_buckets=tuple(sv["feed_buckets"]))


def test_an_idle_slot_walks_no_table_entry():
    eng = _tiny_engine()
    B, bs = eng.max_slots, eng.block_size
    nkv = eng.cfg.n_kv_heads
    max_bps = eng.allocator.table.shape[1]
    fuse = eng.cfg.paged_block_kv // bs
    assert fuse == 2                        # two 16-token blocks a step
    n = 40                                  # three blocks: two fused steps
    eng.alloc_prompt(0, list(range(1, n + 1)))
    tokens = np.zeros((B, n), np.int32)
    pos = np.full((B, n), -1, np.int32)
    tokens[0], pos[0] = np.arange(1, n + 1), np.arange(n)
    eng.prefill(tokens, pos)                # slot 0 live, slot 1 idle
    grid = B * nkv * (max_bps // fuse)
    assert eng.attn_kv_steps_grid == grid
    assert eng.attn_kv_steps_live == nkv * 2
    assert 0 < eng.attn_kv_steps_live < eng.attn_kv_steps_grid
    # the idle slots add nothing: a verify of slot 0 alone adds slot 0's
    # own steps, however many slots the grid has
    before = eng.attn_kv_steps_live
    tok = np.zeros((B, 4), np.int32)
    vpos = np.full((B, 4), -1, np.int32)
    vpos[0] = np.arange(n, n + 4)
    eng.feed(tok, vpos, need_dists=False)
    assert eng.attn_kv_steps_grid == 2 * grid
    assert eng.attn_kv_steps_live - before == nkv * 2
