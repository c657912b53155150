"""A cell at a size a CPU test run can hold: the same harness, serving
path and reference as a real cell, with tiny widths and short mixes."""
from __future__ import annotations

import copy

from chipbench import cells


def tiny_cell(config: str, traffic: str) -> dict:
    """A cell of ``config`` under ``traffic`` (their files) with a
    two-layer model of tiny widths and a mix scaled to seconds; it
    reports ``BENCHMARK.json``'s metrics."""
    c = cells.assemble(f"{config}.{traffic}",
                       cells.HERE / "configs" / f"{config}.json", traffic,
                       1, cells.benchmark())
    m = copy.deepcopy(c["model"])
    m.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
             head_dim=16, num_hidden_layers=2, vocab_size=256)
    m["num_key_value_heads"] = 2 if m["num_key_value_heads"] < 8 else 1
    m["serving"] = dict(m["serving"], slots=4, s_max=256, block_size=16,
                        pool_blocks=48, paged_block_kv=32,
                        feed_buckets=[32, 64])
    mix = copy.deepcopy(c["mix"])
    mix.update(warm_s=1.0, check_tokens=16)
    mix["arrival"] = {"kind": "poisson", "rate_per_s": 6.0}
    mix["prompt"] = dict(mix["prompt"], min=8, max=40, **(
        {"median": 24} if mix["prompt"]["kind"] == "lognormal" else {}))
    mix["output"] = dict(mix["output"], min=16, max=64, **(
        {"median": 40} if mix["output"]["kind"] == "lognormal" else {}))
    mix["device"] = {"ms_per_token": 3.0, "ms_fixed": 0.5}
    c["model"], c["mix"] = m, mix
    return c
