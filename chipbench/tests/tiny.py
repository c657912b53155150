"""A cell at a size a CPU test run can hold: the same harness, serving
path and reference as a real cell, with tiny widths and short mixes."""
from __future__ import annotations

import copy

from chipbench import cells

# A shared-documents mix for the tests of the generator and of the fleet
# path: the shape of the planned fleet cell (16 documents, Zipf
# popularity, four replicas behind prefix affinity), with an arrival rate
# that is a test's, not a measured deployment's.  The chip cell's traffic
# file comes with its rate from a sweep on four chips.
DOCUMENTS = {
    "arrival": {"kind": "poisson", "rate_per_s": 1.0},
    "warm_s": 30,
    "warm_documents": True,
    "prompt": {"kind": "documents", "documents": 16, "zipf_s": 1.0,
               "length": {"kind": "uniform", "min": 2048, "max": 7680},
               "question": {"kind": "uniform", "min": 32, "max": 128}},
    "output": {"kind": "uniform", "min": 64, "max": 256},
    "offload_budget": 0.35,
    "gamma": 4,
    "device": {"ms_per_token": 30.0, "ms_fixed": 2.0},
    "link": {"bandwidth_mbps": 10.0, "rtt_ms": 20.0},
    "router": {"policy": "prefix-affinity", "replica_queue_cap": 0},
    "check_tokens": 150,
}

# the readers of a fleet, which BENCHMARK.json declares for no cell yet
FLEET_METRICS = [
    {"name": "affinity_share", "unit": "%", "better": "higher",
     "source": "program_counter", "layer": "router",
     "moves": "verify_p50_ms"},
    {"name": "replica_busy_skew", "unit": "ratio", "better": "lower",
     "source": "device_trace", "layer": "device", "moves": "verify_p50_ms"},
]


def tiny_cell(config: str, traffic: str, chips: int = 1) -> dict:
    """A cell of ``config`` under ``traffic`` (a traffic file, or
    ``documents`` for the mix above) on ``chips``, with a two-layer
    model of tiny widths and a mix scaled to seconds; it reports
    ``BENCHMARK.json``'s metrics, and on several chips the fleet's."""
    mix = copy.deepcopy(DOCUMENTS) if traffic == "documents" else \
        cells.load_json(cells.HERE / "traffic" / f"{traffic}.json")
    c = cells.assemble(f"{config}.{traffic}", cells.load_json(
        cells.HERE / "configs" / f"{config}.json"), mix, chips,
        cells.benchmark())
    if chips > 1:
        have = {m["name"] for m in c["per_layer"]}
        c["per_layer"] += [m for m in FLEET_METRICS if m["name"] not in have]
    m = copy.deepcopy(c["model"])
    m.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
             head_dim=16, num_hidden_layers=2, vocab_size=256)
    m["num_key_value_heads"] = 2 if m["num_key_value_heads"] < 8 else 1
    # tiny widths read gaps under 0.001 on the CPU; a configuration whose
    # chip limit is not measured yet is held to mistral-7b's
    m["check"] = {"max_logit_gap": m["check"]["max_logit_gap"] or 0.2}
    m["serving"] = dict(m["serving"], slots=4, s_max=256, block_size=16,
                        pool_blocks=48, paged_block_kv=32,
                        feed_buckets=[32, 64])
    mix.update(warm_s=1.0, check_tokens=16)
    mix["arrival"] = {"kind": "poisson", "rate_per_s": 6.0}
    if mix["prompt"]["kind"] == "documents":
        # a few documents of some blocks each, one replica's worth apiece
        mix["prompt"] = dict(mix["prompt"], documents=chips,
                             length={"kind": "uniform", "min": 40, "max": 96},
                             question={"kind": "uniform", "min": 4, "max": 12})
    else:
        mix["prompt"] = dict(mix["prompt"], min=8, max=40, **(
            {"median": 24} if mix["prompt"]["kind"] == "lognormal" else {}))
    mix["output"] = dict(mix["output"], min=16, max=64, **(
        {"median": 40} if mix["output"]["kind"] == "lognormal" else {}))
    mix["device"] = {"ms_per_token": 3.0, "ms_fixed": 0.5}
    c["model"] = m
    return c
