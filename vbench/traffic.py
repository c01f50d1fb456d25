"""The one traffic generator: every mix is a data file it reads.

A mix fixes a *grid* of G (prompt length, output length) pairs, the
quantiles of its two length distributions paired by a fixed shuffle, the
order of the pairs inside each block of G requests and, in an open loop,
the arrival times: one draw of a Poisson process at the mix's rate,
conditioned on its count (a whole number of blocks falls due in the
window, at uniform times, in time order). Order and times are drawn from
the mix's ``schedule_seed``, so every run of every seed does the same
work on the same clock: a pool packs differently under another order and
a tail depends on which bursts a draw holds (PERF.md section 6). The
run's seed decides the token ids (and, elsewhere, the weights).

Keys of a mix file (JSON):
  kind          "open" (arrivals on a schedule) or "saturated" (a backlog
                due at 0; the client keeps ``ahead`` requests waiting
                beyond the slots, so nothing is shed and it never runs dry)
  grid          G, the block size
  schedule_seed the constant that the order and the times are drawn from
  prompt/output {"median", "sigma", "min", "max"}: a log-normal cut to
                [min, max]; sigma 0 gives the median alone
  rate_per_s    open: arrivals a second
  ramp_s        open: seconds of the same arrivals before the window
  ahead         saturated: requests kept waiting beyond the slots
  settle_s      saturated: seconds between the engine first filling its
                slots or its pool and the window's start
  ramp_stagger  saturated: the first this-many requests get 1/n, 2/n, ...
                of their output length, so the first wave does not finish
                together (0: off)
  drain_s       longest wait after the window for first tokens still owed
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
from statistics import NormalDist

import numpy as np

_PAIR_SEED = 0x5EED  # the fixed shuffle that pairs prompt and output grids


@dataclasses.dataclass
class Planned:
    """One request as generated: what to send and, in an open loop, when
    (seconds from the window's start; negative in the ramp)."""

    index: int
    prompt: np.ndarray
    max_new: int
    due_s: float = 0.0
    in_window: bool = True


def load_mix(name: str, root: str) -> dict:
    path = os.path.join(root, "vbench", "traffic", f"{name}.json")
    with open(path) as f:
        mix = json.load(f)
    if mix["kind"] not in ("open", "saturated"):
        raise ValueError(f"{path}: kind must be open or saturated")
    if not isinstance(mix.get("schedule_seed"), int):
        raise ValueError(f"{path}: schedule_seed must be a whole number")
    return mix


def quantile_grid(dist: dict, g: int) -> list[int]:
    """G lengths at the mid-quantiles (j + 0.5) / G of the cut log-normal."""
    lo, hi, med, sigma = dist["min"], dist["max"], dist["median"], dist["sigma"]
    if sigma <= 0:
        return [int(med)] * g
    nd = NormalDist(math.log(med), sigma)
    f_lo, f_hi = nd.cdf(math.log(lo)), nd.cdf(math.log(hi))
    out = []
    for j in range(g):
        q = f_lo + (j + 0.5) / g * (f_hi - f_lo)
        out.append(int(min(hi, max(lo, round(math.exp(nd.inv_cdf(q)))))))
    return out


def length_pairs(mix: dict) -> list[tuple[int, int]]:
    """The mix's G (prompt, output) pairs; the same for every seed."""
    g = mix["grid"]
    prompts = quantile_grid(mix["prompt"], g)
    outputs = quantile_grid(mix["output"], g)
    pairing = np.random.default_rng(_PAIR_SEED).permutation(g)
    return [(prompts[j], outputs[int(pairing[j])]) for j in range(g)]


class Stream:
    """Requests of one mix, in the mix's own order and without end; the
    seed makes their token ids."""

    def __init__(self, mix: dict, seed: int, vocab: int, stagger: int = 0):
        self.stagger = stagger  # the first this-many outputs are cut short
        self.pairs = length_pairs(mix)
        self.g = len(self.pairs)
        self.rng = np.random.default_rng([int(seed), 0x7AFF1C])
        self.order_rng = np.random.default_rng(
            [int(mix["schedule_seed"]), 0x0DE5])
        self.vocab = vocab
        self.index = 0
        self._block: list[int] = []

    def take(self) -> Planned:
        if not self._block:
            self._block = [int(j) for j in self.order_rng.permutation(self.g)]
        plen, olen = self.pairs[self._block.pop(0)]
        prompt = self.rng.integers(1, self.vocab, plen, dtype=np.int32)
        if self.index < self.stagger:
            olen = max(8, math.ceil(olen * (self.index + 1) / self.stagger))
        req = Planned(self.index, prompt, olen)
        self.index += 1
        return req

    def finish_block(self) -> None:
        """Drop the rest of the current block: the next request opens one."""
        self._block = []


def window_count(mix: dict, seconds: float) -> int:
    """Requests due in an open-loop window: rate x seconds, to a whole
    number of blocks (at least one)."""
    g = mix["grid"]
    return g * max(1, round(mix["rate_per_s"] * seconds / g))


def open_schedule(mix: dict, seed: int, vocab: int,
                  seconds: float) -> list[Planned]:
    """Ramp and window of an open loop, in time order. Times within each
    are a Poisson process conditioned on its count: sorted uniforms, the
    mix's one draw of them."""
    stream = Stream(mix, seed, vocab)
    rng = np.random.default_rng([int(mix["schedule_seed"]), 0xA881])
    n_ramp = round(mix["rate_per_s"] * mix["ramp_s"])
    out = []
    for t in np.sort(rng.uniform(-mix["ramp_s"], 0.0, n_ramp)):
        req = stream.take()
        req.due_s, req.in_window = float(t), False
        out.append(req)
    stream.finish_block()
    n = window_count(mix, seconds)
    for t in np.sort(rng.uniform(0.0, seconds, n)):
        req = stream.take()
        req.due_s = float(t)
        out.append(req)
    return out


def backlog(mix: dict, seed: int, vocab: int) -> Stream:
    """A saturated mix's backlog, with its first wave staggered."""
    return Stream(mix, seed, vocab, stagger=int(mix.get("ramp_stagger", 0)))
