"""chip_smoke.py's one-chip phases, run at reduced widths on the CPU.

The script itself refuses to run without a TPU; its phase functions take
the configuration and sizes, so the same checks run here on
``qwen3-0.6b.reduced()`` with bf16 params, as a rehearsal of the chip run.
"""

import os
import sys

import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402
from repro.configs import get_arch  # noqa: E402


@pytest.fixture(scope="module")
def cfg():
    return get_arch(chip_smoke.ARCH).reduced()


def test_train_phase_reduced(cfg):
    out = chip_smoke.train_phase(cfg, global_batch=8, seq=32, slots=3,
                                 steps_per_slot=2, lr=1e-3,
                                 param_dtype=jnp.bfloat16)
    assert out["slots_run"] == 3 and out["compile_count"] == 1
    assert len(out["losses"]) == 3 * 2 + 2   # + the trainer's timing slot


def test_serve_phase_reduced(cfg):
    out = chip_smoke.serve_phase(cfg, n_requests=3, prompt_len=12,
                                 max_new=4, max_batch=2, prefill_chunk=8,
                                 param_dtype=jnp.bfloat16)
    assert out["served"] == 3 and out["decode_compiles"] == 1
    # the logit check read the engine's own prefill with another lane live
    assert out["lanes_live_at_prefill"] == 1
    assert out["served_tokens_checked"] == 3 * 4


def test_refuses_without_tpu(monkeypatch, capsys):
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py"])
    assert chip_smoke.main() == 2
    captured = capsys.readouterr()
    assert captured.out == ""
    assert "no TPU" in captured.err
