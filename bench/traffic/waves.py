"""Closed-loop waves: ``slots`` requests handed to the engine at once.

``ServeEngine.generate`` runs a list to completion and admits nothing
while it runs, so the load is a closed loop of waves: each wave is one
full batch, and the next follows as soon as the last finishes.

The schedule is fixed by the mix's data file, not by the run's seed:
a deck of ``schedule_waves * slots`` requests whose prompt and output
lengths hit the mix's weights exactly (largest-remainder rounding),
shuffled once with ``schedule_seed`` and cut into waves.  Every seed
therefore serves the same sizes in the same wave order, so the work in
a window does not change with the seed.  The seed draws the prompt
tokens and the order of the requests inside each wave, afresh on each
pass over the deck.
"""

from __future__ import annotations

import dataclasses
from typing import Iterator

import numpy as np


@dataclasses.dataclass(frozen=True)
class Req:
    prompt: np.ndarray      # (prompt_len,) int32 token ids
    max_new_tokens: int


def exact_counts(weights, n: int) -> list[int]:
    """Split ``n`` items over ``weights`` by largest remainder."""
    w = np.asarray(weights, np.float64)
    raw = w / w.sum() * n
    counts = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - counts), kind="stable")[:n - counts.sum()]:
        counts[i] += 1
    return counts.tolist()


def schedule(mix: dict) -> list[list[tuple[int, int]]]:
    """The fixed wave schedule: ``[[(prompt_len, new_tokens), ...], ...]``."""
    slots, n_waves = mix["slots"], mix["schedule_waves"]
    n = slots * n_waves
    rng = np.random.default_rng(mix["schedule_seed"])

    def deck(buckets, weights):
        d = np.repeat(buckets, exact_counts(weights, n))
        return rng.permutation(d).tolist()

    prompts = deck(mix["prompt_buckets"], mix["prompt_weights"])
    outputs = deck(mix["output_buckets"], mix["output_weights"])
    pairs = list(zip(prompts, outputs))
    return [pairs[i:i + slots] for i in range(0, n, slots)]


def warmup_waves(mix: dict, vocab: int) -> list[list[Req]]:
    """One wave per prompt bucket, its longest prompt that bucket.

    Every padded prefill length the window can meet is compiled here;
    two output tokens also compile the decode step where the mix
    decodes at all."""
    new = min(2, max(mix["output_buckets"]))
    rng = np.random.default_rng(0)
    return [[Req(rng.integers(0, vocab, b).astype(np.int32), new)
             for _ in range(mix["slots"])]
            for b in mix["prompt_buckets"]]


def waves(mix: dict, seed: int, vocab: int) -> Iterator[list[Req]]:
    """Endless waves: the schedule, over and over, tokens from ``seed``."""
    sched = schedule(mix)
    rng = np.random.default_rng(seed)
    while True:
        for wave in sched:
            order = rng.permutation(len(wave))
            yield [Req(rng.integers(0, vocab, wave[j][0]).astype(np.int32),
                       int(wave[j][1]))
                   for j in order]
