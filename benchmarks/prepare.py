"""Train (once) and cache the tiny SLM/LLM pair used by the paper-claim
benchmarks.  Checkpoints land in results/ckpt/; reruns load from disk.
"""
from __future__ import annotations

import os

import numpy as np

import jax

from repro.checkpoint import io as ckpt
from repro.configs.synera_pair import tiny_pair
from repro.data.synthetic import SyntheticTask, TaskSpec
from repro.launch.train import train
from repro.models import model as M

CKPT_DIR = "results/ckpt"
VOCAB = 64
STEPS_SLM = 250
STEPS_LLM = 400


def get_pair(steps_slm: int = STEPS_SLM, steps_llm: int = STEPS_LLM,
             force: bool = False):
    """Returns (slm_cfg, slm_params, llm_cfg, llm_params, task)."""
    slm_cfg, llm_cfg = tiny_pair(vocab=VOCAB)
    task = SyntheticTask(TaskSpec(vocab=VOCAB))
    os.makedirs(CKPT_DIR, exist_ok=True)
    out = []
    corpus = None
    for cfg, steps in ((slm_cfg, steps_slm), (llm_cfg, steps_llm)):
        path = f"{CKPT_DIR}/{cfg.name}.npz"
        like = jax.eval_shape(lambda k, c=cfg: M.init_params(c, k),
                              jax.ShapeDtypeStruct((2,), np.uint32))
        if os.path.exists(path) and not force:
            params = ckpt.load(path, like)
            print(f"loaded {cfg.name} from {path}")
        else:
            if corpus is None:
                corpus, _ = task.corpus(n_sequences=64, length=2048, seed=0)
            print(f"training {cfg.name} ({cfg.param_count()/1e6:.1f}M) "
                  f"for {steps} steps...")
            params, _ = train(cfg, steps=steps, corpus=corpus,
                              log_every=100, ckpt_path=path)
        out.append(params)
    return slm_cfg, out[0], llm_cfg, out[1], task


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    get_pair(force="--force" in __import__("sys").argv)
