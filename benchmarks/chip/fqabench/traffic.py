"""One generator for every traffic mix.

A mix is a JSON file of parameters under ``traffic/``.  Every seed gets
the same multiset of prompt lengths, output lengths and inter-arrival
gaps (stratified quantiles of the stated distributions), in an order and
with token ids drawn from the seed, so two seeds do the same work.

Two kinds of arrival:

* ``open_loop``: ``rate_per_s`` x window seconds requests, due at fixed
  times whatever the server does.
* ``backlog``: offline jobs; from the window's start the queue is topped
  up to ``backlog_per_slot`` x ``n_slots`` requests before every step.
"""

from __future__ import annotations

import dataclasses
import itertools
from statistics import NormalDist
from typing import Iterator, List, Optional

import numpy as np

_REQUIRED = {"kind", "prompt", "output", "n_slots", "cache_len",
             "check_requests"}


@dataclasses.dataclass
class Job:
    """One request as the generator makes it."""

    index: int
    prompt: np.ndarray            # (T,) int32
    max_new_tokens: int
    due_s: Optional[float]        # offset from the window's start; None = now


def _seed_seq(seed: int, *words: int) -> np.random.SeedSequence:
    return np.random.SeedSequence([seed % (1 << 63), *words])


def quantile_lengths(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths at the mid-quantiles (i + 0.5) / n of ``dist``,
    rounded and clipped to ``[min, max]``, in ascending order."""
    lo, hi = int(dist["min"]), int(dist["max"])
    q = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "uniform":
        vals = lo + q * (hi - lo + 1) - 0.5
    elif kind == "lognormal":
        z = np.asarray([NormalDist().inv_cdf(float(x)) for x in q])
        vals = float(dist["median"]) * np.exp(float(dist["sigma"]) * z)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.rint(vals), lo, hi).astype(np.int64)


def arrival_offsets(n: int, seconds: float, seed: int) -> np.ndarray:
    """Due times of ``n`` open-loop requests in ``[0, seconds)``: the
    exponential gaps' mid-quantiles, scaled to sum to ``seconds`` and
    shuffled by the seed; the first request is due at 0."""
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    gaps *= seconds / gaps.sum()
    rng = np.random.default_rng(_seed_seq(seed, 1))
    gaps = gaps[rng.permutation(n)]
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


@dataclasses.dataclass
class Mix:
    name: str
    spec: dict

    def __post_init__(self):
        missing = _REQUIRED - set(self.spec)
        if missing:
            raise ValueError(f"traffic {self.name}: missing {sorted(missing)}")
        if self.kind not in ("open_loop", "backlog"):
            raise ValueError(f"traffic {self.name}: unknown kind {self.kind!r}")
        longest = int(self.spec["prompt"]["max"]) + int(
            self.spec["output"]["max"])
        if longest > self.cache_len:
            raise ValueError(f"traffic {self.name}: prompt + output up to "
                             f"{longest} tokens overflows cache_len "
                             f"{self.cache_len}")

    @property
    def kind(self) -> str:
        return self.spec["kind"]

    @property
    def n_slots(self) -> int:
        return int(self.spec["n_slots"])

    @property
    def cache_len(self) -> int:
        return int(self.spec["cache_len"])

    @property
    def prompt_range(self) -> range:
        p = self.spec["prompt"]
        return range(int(p["min"]), int(p["max"]) + 1)

    @property
    def backlog(self) -> int:
        return int(self.spec["backlog_per_slot"]) * self.n_slots

    def open_loop_count(self, seconds: float) -> int:
        return max(1, int(round(float(self.spec["rate_per_s"]) * seconds)))

    def _sizes(self, n: int, seed: int, word: int):
        rng = np.random.default_rng(_seed_seq(seed, 2, word))
        p = quantile_lengths(self.spec["prompt"], n)[rng.permutation(n)]
        o = quantile_lengths(self.spec["output"], n)[rng.permutation(n)]
        return p, o

    def _job(self, seed: int, index: int, plen: int, olen: int,
             vocab: int, due: Optional[float]) -> Job:
        rng = np.random.default_rng(_seed_seq(seed, 3, index))
        prompt = rng.integers(0, vocab, int(plen), dtype=np.int64)
        return Job(index, prompt.astype(np.int32), int(olen), due)

    def open_loop(self, seed: int, seconds: float, vocab: int) -> List[Job]:
        """Every request due in a window of ``seconds``, by due time."""
        n = self.open_loop_count(seconds)
        p, o = self._sizes(n, seed, 0)
        due = arrival_offsets(n, seconds, seed)
        return [self._job(seed, i, p[i], o[i], vocab, float(due[i]))
                for i in range(n)]

    def stream(self, seed: int, vocab: int) -> Iterator[Job]:
        """Endless offline jobs, in blocks of ``block`` that each hold the
        same multiset of sizes in a seeded order."""
        block = int(self.spec["block"])
        i = 0
        for b in itertools.count():
            p, o = self._sizes(block, seed, 1 + b)
            for j in range(block):
                yield self._job(seed, i, p[j], o[j], vocab, None)
                i += 1

    def warm_shapes(self, bucket_of) -> List[tuple]:
        """(bucket, group size) of every prefill the mix can cause, given
        the engine's ``bucket_of(prompt_len)``: each bucket its prompt
        range reaches, at every admission group size up to ``n_slots``.
        Each bucket is paired with the longest prompt that falls in it."""
        longest = {}
        for n in self.prompt_range:
            longest[bucket_of(n)] = n
        return [(b, longest[b], g) for b in sorted(longest)
                for g in range(1, self.n_slots + 1)]


def check_sample(finished: list, k: int, seed: int) -> list:
    """The requests whose served tokens the reference checks: the one
    with the most served tokens, and ``k - 1`` others drawn from the
    seed."""
    if not finished:
        return []
    order = sorted(finished, key=lambda r: (-len(r.output), r.rid))
    longest, rest = order[0], sorted(order[1:], key=lambda r: r.rid)
    rng = np.random.default_rng(_seed_seq(seed, 4))
    pick = rng.permutation(len(rest))[:max(0, k - 1)]
    return [longest] + [rest[i] for i in sorted(pick)]

