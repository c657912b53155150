"""Cells as data: ``BENCHMARK.json`` names a cell's configuration,
traffic mix and chips; each lives in a file of its own, found by name."""
from __future__ import annotations

import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def cell(name: str) -> dict:
    """The ``workloads`` entry of ``name`` joined with its configuration
    and traffic files and the metrics it reports."""
    bench = benchmark()
    found = [w for w in bench["workloads"] if w["name"] == name]
    if not found:
        raise SystemExit(f"chipbench: no workload {name!r} in BENCHMARK.json")
    w = found[0]
    conf = [c for c in bench["configs"] if c["name"] == w["config"]][0]
    return assemble(name, load_json(ROOT / conf["file"]),
                    load_json(HERE / "traffic" / f"{w['traffic']}.json"),
                    w["chips"], bench)


def assemble(name: str, model: dict, mix: dict, chips: int,
             bench: dict) -> dict:
    """A cell from its configuration and traffic mix; ``bench`` supplies
    the metrics, those with a ``workloads`` key only where it names this
    cell."""
    return dict(
        name=name, chips=chips, model=model, mix=mix,
        end_to_end=[m for m in bench["end_to_end"]
                    if name in m.get("workloads", [name])],
        per_layer=[m for m in bench["per_layer"]
                   if name in m.get("workloads", [name])])


def model_config(model: dict):
    """The program's ``ModelConfig`` for a configuration file: the dense
    GQA decoder on the paged Pallas path, as served."""
    from repro.configs.base import ModelConfig

    sv = model["serving"]
    d, nh = model["hidden_size"], model["num_attention_heads"]
    return ModelConfig(
        name=model["name"], family="dense",
        n_layers=model["num_hidden_layers"], d_model=d, n_heads=nh,
        n_kv_heads=model["num_key_value_heads"],
        d_ff=model["intermediate_size"], vocab=model["vocab_size"],
        head_dim=model.get("head_dim") or d // nh,
        qkv_bias=bool(model["qkv_bias"]), rope_theta=model["rope_theta"],
        norm_eps=model["rms_norm_eps"],
        tie_embeddings=bool(model["tie_word_embeddings"]),
        source=model["source"], max_verify_chunk=sv["chunk"],
        cache_impl="paged", kv_block_size=sv["block_size"],
        paged_block_kv=sv["paged_block_kv"], share_prefix=True,
        retain_prefix=True, attn_impl="pallas", remat=False,
        dtype=model["torch_dtype"])
