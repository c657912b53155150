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

ROOT = Path(__file__).resolve().parents[2]


def _env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    env.update(extra)
    return env


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_cell_end_to_end(trace):
    p = subprocess.run(
        [sys.executable, "-m", "chipbench.tests.rehearse", "mistral-7b",
         "chat", str(trace)], cwd=ROOT, capture_output=True, text=True,
        timeout=600, env=_env())
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    keys = ["correct", "attempted", "failed", "metrics", "device"]
    assert list(res) == keys + (["breakdown"] if trace else []) + ["checks"]
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["count"] == 1
    names = set(res["metrics"])
    if trace:
        assert {"padded_row_share", "prefix_hit_share"} <= names
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert names == {m["name"] for m in cells.benchmark()["end_to_end"]}
    assert all(v["value"] >= 0 for v in res["metrics"].values())
    assert p.stderr.strip().splitlines()[-1].startswith("check ")
    serve = [json.loads(line.split(" ", 2)[2]) for line in
             p.stdout.splitlines() if line.startswith("chipbench serve ")]
    assert serve and serve[0]["compiles_in_window"] == 0
    assert 0 < serve[0]["rows_valid"] < serve[0]["rows_computed"]


def test_the_command_refuses_to_measure_without_a_tpu():
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "mistral-7b.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=ROOT,
        capture_output=True, text=True, timeout=300, env=_env())
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip() or not p.stdout.strip().splitlines()[
        -1].startswith("{")
