"""One generator for every traffic mix: a mix is a JSON file of parameters.

A mix fixes a pool of request shapes (prompt and output lengths) from
quantiles of its length distributions, so every seed serves the same
work.  The loop is closed: client lane j sends the pool's shapes in a
fixed order, (offset_j + k * stride) mod pool for its k-th request, so
the lanes' timelines, and the tokens a window holds, are the same for
every seed; the seed decides which client (request id) runs which lane,
and draws the token ids.

Keys of a mix file:

    loop          "closed": ``clients`` callers, each sends its next
                  request as soon as its last one finishes
    clients       number of callers (at most ``pool``)
    prompt_len    {"dist": "log_uniform" | "fixed", "min", "max"}
    output_len    the same, for generated tokens
    pool          number of request shapes in the pool
    stagger_first the first request of client c has its lengths scaled
                  by (c + 1) / clients, so the slots of a fresh engine
                  do not all finish together
    sampling      "greedy" (the only mode the engine serves)
"""
from __future__ import annotations

import math
from typing import List

import numpy as np


def quantiles(dist: dict, n: int) -> np.ndarray:
    """n lengths at the mid-quantiles (i + 0.5) / n of ``dist``."""
    lo, hi = int(dist["min"]), int(dist.get("max", dist["min"]))
    kind = dist.get("dist", "log_uniform")
    u = (np.arange(n) + 0.5) / n
    if kind == "fixed" or lo == hi:
        return np.full(n, lo, np.int64)
    if kind != "log_uniform":
        raise ValueError(f"unknown length distribution {kind!r}")
    x = np.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def _stride(n: int) -> int:
    """The smallest step past n / 2 that is coprime to n: a walk by it
    visits all of range(n) and alternates its halves."""
    return next(s for s in range(n // 2 + 1, n + 1) if math.gcd(s, n) == 1)


def shape_pool(mix: dict) -> np.ndarray:
    """[pool, 2] (prompt, output) lengths; seed-independent.  Prompt and
    output quantiles are paired by a fixed stride, so long prompts meet
    both short and long outputs."""
    n = int(mix["pool"])
    p = quantiles(mix["prompt_len"], n)
    o = quantiles(mix["output_len"], n)
    return np.stack([p, o[(np.arange(n) * _stride(n)) % n]], axis=1)


def lane_shape(mix: dict, lane: int, k: int) -> int:
    """Pool index of the k-th request of closed-loop lane ``lane``.  In
    each round k the lanes take distinct shapes, and two rounds cover the
    pool once when there are half as many clients as shapes."""
    n, clients = int(mix["pool"]), int(mix["clients"])
    return (lane * n // clients + k * _stride(n)) % n


class Traffic:
    """Requests of one mix for one seed.  The caller's loop asks for the
    ``initial`` requests once, and for ``finished(now, rid)`` after each
    completion: the client whose request finished sends its next one."""

    def __init__(self, mix: dict, seed: int, vocab: int, max_len: int,
                 make_request):
        if mix.get("loop") != "closed":
            raise ValueError("a mix's loop must be \"closed\"")
        if mix.get("sampling", "greedy") != "greedy":
            raise ValueError("the engine serves greedy requests only")
        self.mix = mix
        self.vocab = vocab
        self.make_request = make_request
        self.rng = np.random.default_rng([seed, 7])
        self.pool = shape_pool(mix)
        if int(self.pool.sum(axis=1).max()) > max_len:
            raise ValueError(f"mix {mix.get('name')!r}: a prompt plus its "
                             f"output exceeds max_len {max_len}")
        if int(mix["clients"]) > len(self.pool):
            raise ValueError("a closed loop needs a pool at least as large "
                             "as its clients")
        self._next_rid = 0
        self._lane = {}                # rid -> (lane, k)

    def _request(self, now: float, lane: int, k: int, scale: float = 1.0):
        p, o = (int(x) for x in self.pool[lane_shape(self.mix, lane, k)])
        p, o = max(1, round(p * scale)), max(1, round(o * scale))
        prompt: List[int] = self.rng.integers(0, self.vocab, p).tolist()
        self._lane[self._next_rid] = (lane, k)
        req = self.make_request(self._next_rid, prompt, o, now)
        self._next_rid += 1
        return req

    def initial(self, now: float) -> list:
        clients = int(self.mix["clients"])
        stagger = bool(self.mix.get("stagger_first"))
        return [self._request(now, int(lane), 0,
                              (lane + 1) / clients if stagger else 1.0)
                for lane in self.rng.permutation(clients)]

    def finished(self, now: float, rid: int) -> list:
        """The client whose request ``rid`` just finished sends its next
        one."""
        lane, k = self._lane.pop(rid)
        return [self._request(now, lane, k + 1)]
