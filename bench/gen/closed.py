"""Closed loop: an offline batch whose queue never runs dry.

Parameters (``bench/traffic/<mix>.json``): ``prompt`` and ``output``
length distributions (``median``, ``sigma``, ``min``, ``max``), ``pool``
(requests in the set; it repeats in a new order when used up) and
``depth`` (requests kept outstanding, above the slot count). The first
``slots`` requests, a fixed part of the set, run to a share of their
drawn length spread evenly over (0, 1], so that completions and
admissions are spread through the window from the start. The set then
comes in blocks of prompt lengths (``_common.blocked``).
"""
from __future__ import annotations

import math

import numpy as np

from _common import (blocked, layout_rng, lognormal, pages_of,
                     shuffled, tokens)


class Traffic:
    open_loop = False
    shares_prefix = False
    warm_s = 0.0

    def __init__(self, p: dict, seed: int, vocab: int, slots: int,
                 seconds: float):
        self.vocab, self.slots = vocab, slots
        self.rng = np.random.default_rng(seed)
        self.depth = max(p["depth"], slots)
        self.min_prompt = p["prompt"]["min"]
        self.max_prompt = p["prompt"]["max"]
        lay = layout_rng()
        n = p["pool"]
        self.pairs = list(zip(lognormal(n, p["prompt"]),
                              shuffled(lay, lognormal(n, p["output"]))))
        first = self.pairs[::max(1, n // slots)][:slots]   # across sizes
        shares = (np.arange(slots) + 0.5) / slots
        self.first = [(pl, max(1, math.ceil(ol * s)))
                      for (pl, ol), s in zip(first, shuffled(lay, shares))]
        self._queue = list(shuffled(self.rng, np.arange(slots)))
        self._queue = [self.first[i] for i in self._queue][::-1]

    def page_counts(self, page_size: int):
        """(pages a retirement publishes, pages an admission gathers)."""
        return ({pages_of(int(a + b), page_size)
                 for a, b in self.pairs + self.first}, set())

    def setup_requests(self):
        return []

    def poll(self, now: float, outstanding: int):
        """Requests due by ``now``: enough to keep ``depth`` outstanding."""
        out = []
        while outstanding + len(out) < self.depth:
            if not self._queue:
                order = blocked(self.rng, [a for a, _ in self.pairs])
                self._queue = [self.pairs[i] for i in order][::-1]
            plen, olen = self._queue.pop()
            out.append({"prompt": tokens(self.rng, int(plen), self.vocab),
                        "max_new": int(olen), "due": now})
        return out

    def next_due(self):
        return None

    def on_finish(self, req: dict, t: float):
        pass
