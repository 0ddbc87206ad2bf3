"""A small stand-in for a cell, for running a driver on the CPU: the
program's reduced qwen3 (2 layers, width 128, vocabulary 512) and its
configuration file, with short traffic."""

from __future__ import annotations

import copy
import time

import harness
from harness import Run

CONFIG = {
    "program_arch": "qwen3-0.6b", "reference": "dense_lm",
    "hidden_size": 128, "intermediate_size": 256, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "vocab_size": 512, "rope_theta": 1000000.0, "rms_norm_eps": 1e-06,
    "qk_norm": True,
}

TRAIN = {
    "kind": "train", "global_batch": 4, "seq": 32, "mode": "ring",
    "steps_per_slot": 2, "compared_steps": 3,
    "optimizer": {"name": "adamw", "lr": 0.001, "b1": 0.9, "b2": 0.95,
                  "eps": 1e-08, "weight_decay": 0.1},
    "limits": {"loss_gap": 1e-3, "grad_norm_gap": 1e-2,
               "change_norm_gap": 2e-2},
}

SERVE = {
    "kind": "serve", "max_seq": 64, "trace_seconds": 1, "max_batch": 4,
    "max_batch_rehearsal": "none (CPU test)", "rate_per_s": 20.0,
    "mix": {"shape_seed": 1,
            "prompt_len": {"mean": 10, "sigma": 1.0, "min": 4, "max": 32},
            "answer_len": {"mean": 8, "sigma": 0.8, "min": 2, "max": 32},
            "drain_s": 30},
    "check": {"requests": 4},
    "limits": {"served_logit_gap": 0.02},
}


def arch():
    from repro.configs import get_arch

    return get_arch("qwen3-0.6b").reduced()


def run(traffic: dict, seed: int = 7, seconds: float = 1.0) -> Run:
    r = Run(workload={"name": "small", "chips": 1}, config=dict(CONFIG),
            traffic=copy.deepcopy(traffic), seed=seed, seconds=seconds,
            trace=False, t_process=time.perf_counter())
    r.counter = harness.CompileCounter().install()
    return r
