"""The one traffic generator: reads a mix's parameter file
(`traffic/<mix>.json`) and makes a run's requests from `--seed`.

A mix fixes a set of request sizes (and, for timed arrivals, of gaps
between them) from its own `size_seed`, offered in the set's own order.
The run seed draws only the token ids, so every seed offers the same work.

Backlog traffic opens its window in steady serving: the first batch is
already running, each of its requests part-way through its output
(`Generator.in_flight`), so that rows finish, and queued requests are
admitted, at their steady rate all through the window.

Parameters:
  arrivals       {"kind": "backlog"}: the client keeps at least one
                 batch of requests queued behind the running batch;
                 {"kind": "poisson", "rate_per_s": r}: open loop, gaps
                 exponential with mean 1/r
  prompt_tokens  {"median", "sigma", "min", "max"}: lognormal, clipped
  output_tokens  the same, for the tokens each request asks for
  size_seed      seed of the size and gap set
  n_requests     size of the set; a run that needs more wraps round it
"""
from __future__ import annotations

import dataclasses
import json

import numpy as np


@dataclasses.dataclass(frozen=True)
class Offer:
    """One request as the client offers it: `due` is seconds after the
    window opens (None for backlog traffic, due when enqueued)."""
    index: int
    prompt: np.ndarray
    max_new_tokens: int
    due: float | None


def load(path: str) -> dict:
    with open(path) as f:
        mix = json.load(f)
    kind = mix["arrivals"]["kind"]
    if kind not in ("backlog", "poisson"):
        raise ValueError(f"{path}: unknown arrivals kind {kind!r}")
    if kind == "poisson" and not mix["arrivals"].get("rate_per_s", 0) > 0:
        raise ValueError(f"{path}: poisson arrivals need rate_per_s > 0")
    return mix


def _lognormal(rng, spec: dict, n: int) -> np.ndarray:
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], size=n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def size_set(mix: dict) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(prompt lengths, output lengths, gaps in s) of the mix's fixed set,
    in the set's own order; gaps are zero for backlog traffic."""
    rng = np.random.default_rng(mix["size_seed"])
    n = mix["n_requests"]
    prompts = _lognormal(rng, mix["prompt_tokens"], n)
    outputs = _lognormal(rng, mix["output_tokens"], n)
    arr = mix["arrivals"]
    if arr["kind"] == "poisson":
        gaps = rng.exponential(1.0 / arr["rate_per_s"], size=n)
    else:
        gaps = np.zeros(n)
    return prompts, outputs, gaps


class Generator:
    """Offers of one run, made on demand: `offer(i)` is the i-th request."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix, self.vocab = mix, vocab
        self.backlog = mix["arrivals"]["kind"] == "backlog"
        self.prompts, self.outputs, gaps = size_set(mix)
        self.due = np.cumsum(gaps)
        self.token_seed = seed

    def due_at(self, i: int) -> float:
        """Seconds after the window opens at which offer i is due."""
        n = len(self.due)
        return float(self.due[-1] * (i // n) + self.due[i % n])

    def _tokens(self, stream: int, i: int, n: int) -> np.ndarray:
        rng = np.random.default_rng([self.token_seed, stream, i])
        return rng.integers(0, self.vocab, size=n,
                            dtype=np.int64).astype(np.int32)

    def offer(self, i: int) -> Offer:
        j = i % len(self.prompts)
        prompt = self._tokens(2, i, int(self.prompts[j]))
        due = None if self.backlog else self.due_at(i)
        return Offer(i, prompt, int(self.outputs[j]), due)

    def in_flight(self, i: int, batch: int) -> Offer:
        """Offer i of the `batch` requests running when the window opens,
        as steady serving holds them: rows finish one per mean output
        over `batch` steps, the rate of a saturated batch, so offer i has
        (i + 0.5) of those spacings still to serve (at most its whole
        output), and what it has served so far is fed with its prompt
        (ids drawn from the seed), so its context is as long as steady
        serving's."""
        o = self.offer(i)
        spacing = float(np.mean(self.outputs)) / batch
        left = min(o.max_new_tokens, max(1, round((i + 0.5) * spacing)))
        served = self._tokens(4, i, o.max_new_tokens - left)
        return Offer(i, np.concatenate([o.prompt, served]), left, o.due)
