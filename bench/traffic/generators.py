"""The one traffic generator: open-loop requests from a mix's data file and a seed.

A mix file (``bench/traffic/<mix>.json``) gives its arrival rate, its pre-roll,
its length distributions and its ``block``.  Requests come in blocks of
``block``: each block's prompt lengths, output lengths and inter-arrival gaps
are the ``block`` stratified quantiles of those distributions, and the run's
seed draws their order within each block and the token ids.  So every seed
offers the same sizes and arrivals in every block, in another order: a
window that serves about a block of requests gets the same work from every
seed.  (With one order drawn over the whole run, the seed decided which
lengths met in a window, and moved tokens/s by a fifth.)

Arrivals are a Poisson process at ``rate_per_s`` (the exponential gaps of
``launch/serve_engine.poisson_workload``), starting ``preroll_s`` before the
window opens, so the window sees a full pool.  A rate above the engine's
knee keeps every slot busy and a queue waiting: a cell at such a rate is
judged on tokens per second, one below it on its tails.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np

SEED_SALT = 0x5EED_BE7C
KINDS = ("poisson",)


def rng_for(seed: int, *salt: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), SEED_SALT, *salt]))


@dataclasses.dataclass
class Arrival:
    due: float  # seconds relative to window open (negative: pre-roll)
    prompt: np.ndarray  # int32 token ids
    max_new: int


def lognormal_quantiles(n: int, dist: dict) -> np.ndarray:
    """``n`` stratified quantiles of a clipped lognormal, as whole tokens."""
    inv = NormalDist().inv_cdf
    vals = [dist["median"] * math.exp(dist["sigma"] * inv((i + 0.5) / n))
            for i in range(n)]
    return np.clip(np.rint(vals), dist["min"], dist["max"]).astype(np.int64)


def exponential_quantiles(n: int, rate: float) -> np.ndarray:
    return np.array([-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)])


def arrivals_for(mix: dict, seconds: float) -> int:
    """How many requests a run offers (pre-roll, the window and a tail), in
    whole blocks."""
    rate, preroll, k = float(mix["rate_per_s"]), float(mix["preroll_s"]), int(mix["block"])
    return k * math.ceil((math.ceil(rate * (preroll + seconds) * 1.25) + 8) / k)


def poisson_schedule(mix: dict, seed: int, vocab: int, seconds: float) -> list[Arrival]:
    """Every arrival of a run: whole blocks, each in the order the seed draws."""
    if mix["kind"] not in KINDS:
        raise ValueError(f"unknown traffic kind {mix['kind']!r}; known: {KINDS}")
    rate, k = float(mix["rate_per_s"]), int(mix["block"])
    rng = rng_for(seed, 1)
    gaps, prompts, outputs = [], [], []
    for _ in range(arrivals_for(mix, seconds) // k):
        gaps += list(rng.permutation(exponential_quantiles(k, rate)))
        prompts += list(rng.permutation(lognormal_quantiles(k, mix["prompt"])))
        outputs += list(rng.permutation(lognormal_quantiles(k, mix["output"])))
    due = np.cumsum(gaps) - float(mix["preroll_s"])
    return [Arrival(float(t), rng.integers(0, vocab, int(p), dtype=np.int64).astype(np.int32),
                    int(o)) for t, p, o in zip(due, prompts, outputs)]
