"""The one traffic generator: every serving mix is a data file it reads.

Open loop. Inter-arrival gaps are exponential (Poisson arrivals at the
cell's rate); prompt and answer lengths are lognormal with the stated
means and sigmas, clipped. Gaps and lengths, and their order, come from the
mix's own ``shape_seed``, so that every run gets the same arrivals of the
same sizes: the queueing they cause, and with it a tail over a few dozen
requests, depends on their order as much as on their sizes. The run's seed
draws the token ids (and, in the driver, the weights). Requests due inside
the window are the ones measured; arrivals go on after it at the same
rate, so that the window's last requests finish under the same load.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np


@dataclasses.dataclass
class Arrival:
    index: int
    due_s: float            # offset from the window's start
    prompt: np.ndarray      # int32 token ids
    max_new: int
    in_window: bool


def _lognormal(rng, n: int, spec: Dict) -> np.ndarray:
    """Lognormal lengths with mean ``spec['mean']`` (of the lengths, not of
    their log), clipped to [min, max]."""
    sigma = spec["sigma"]
    mu = np.log(spec["mean"]) - sigma * sigma / 2.0
    x = rng.lognormal(mu, sigma, size=n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def _shapes(mix: Dict, rate: float, n: int):
    rng = np.random.default_rng(mix["shape_seed"])
    gaps = rng.exponential(1.0 / rate, size=n)
    return (gaps, _lognormal(rng, n, mix["prompt_len"]),
            _lognormal(rng, n, mix["answer_len"]))


def arrivals(mix: Dict, rate: float, seed: int, seconds: float, vocab: int
             ) -> List[Arrival]:
    """Every arrival of a run at ``rate`` per second: those due in
    ``[0, seconds)`` first, then those of the ``drain_s`` after it."""
    # enough draws that the window and the drain are both covered
    n = int(rate * (seconds + mix["drain_s"]) * 2 + 50)
    gaps, prompts, answers = _shapes(mix, rate, n)
    due = np.cumsum(gaps)
    n_win = int(np.searchsorted(due, seconds))
    rng = np.random.default_rng(seed)
    out: List[Arrival] = []
    for i in range(n):
        if due[i] >= seconds + mix["drain_s"]:
            break
        out.append(Arrival(
            index=i, due_s=float(due[i]),
            prompt=rng.integers(0, vocab, int(prompts[i]), dtype=np.int32),
            max_new=int(answers[i]), in_window=i < n_win))
    return out
