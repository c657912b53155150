"""CPU rehearsal of the harness: a tiny cell runs end to end through the
replay device and ``SyneraServer``, the Pallas kernels in interpret
mode, and the reference; the last line has the result's keys.  The
command itself refuses to measure without a TPU."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench import cells
from chipbench.harness import _load_reader

ROOT = Path(__file__).resolve().parents[2]


def _env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    env.update(extra)
    return env


FOUR_DEVICES = {"XLA_FLAGS": "--xla_force_host_platform_device_count=4"}


@pytest.mark.parametrize("config,traffic,trace,chips", [
    pytest.param("mistral-7b", "chat", 0, 1, id="0"),
    pytest.param("mistral-7b", "chat", 1, 1, id="1"),
    # four replicas on four virtual devices behind the router
    pytest.param("qwen2-7b", "documents", 1, 4, id="fleet4"),
])
def test_tiny_cell_end_to_end(config, traffic, trace, chips):
    p = subprocess.run(
        [sys.executable, "-m", "chipbench.tests.rehearse", config,
         traffic, str(trace), str(chips)], cwd=ROOT, capture_output=True,
        text=True, timeout=600,
        env=_env(**(FOUR_DEVICES if chips > 1 else {})))
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    keys = ["correct", "attempted", "failed", "metrics", "device"]
    assert list(res) == keys + (["breakdown"] if trace else []) + ["checks"]
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["count"] == chips
    names = set(res["metrics"])
    if trace:
        assert {"padded_row_share", "prefix_hit_share"} <= names
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert names == {m["name"] for m in cells.benchmark()["end_to_end"]}
    assert all(v["value"] >= 0 for v in res["metrics"].values())
    if chips > 1:
        assert res["metrics"]["prefix_hit_share"]["value"] > 0
        assert res["metrics"]["affinity_share"]["value"] > 0
        assert {g[0].split(":")[0] for g in res["breakdown"]["idle_gaps"]
                } == {f"TPU{i}" for i in range(chips)}
    assert p.stderr.strip().splitlines()[-1].startswith("check ")
    lines = {line.split(" ", 2)[1]: json.loads(line.split(" ", 2)[2])
             for line in p.stdout.splitlines()
             if line.startswith("chipbench ")}
    serve = lines["serve"]
    assert serve["compiles_in_window"] == 0
    assert 0 < serve["rows_valid"] < serve["rows_computed"]
    if chips > 1:
        # the router placed the window's sessions by affinity, degraded
        # none, and every replica served a checked stream
        counters = serve["counters"]
        assert counters["degraded_streams"] == 0
        assert _load_reader("affinity_share")(dict(
            base={"opened": 0, "affinity_hits": 0}, end=counters)) > 0
        assert lines["check"]["streams"] >= chips


def test_the_command_refuses_to_measure_without_a_tpu():
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "mistral-7b.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=ROOT,
        capture_output=True, text=True, timeout=300, env=_env())
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip() or not p.stdout.strip().splitlines()[
        -1].startswith("{")
