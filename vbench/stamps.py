"""Arithmetic on the client's stamps: the only place a latency or a rate
is computed. Times are seconds from the window's start."""

from __future__ import annotations

import dataclasses
import math
from typing import Optional


@dataclasses.dataclass
class Record:
    """What the client saw of one request."""

    index: int
    prompt_len: int
    max_new: int
    due_s: float                 # when it was due (open loop) or sent
    in_window: bool              # due inside the window
    sent_s: float = math.nan     # when submit() was called
    depart_s: float = math.nan   # Request.t_depart_ns: left the queue
    stamps: list = dataclasses.field(default_factory=list)   # one a token
    tokens: list = dataclasses.field(default_factory=list)
    status: Optional[str] = None  # the engine's terminal, None: unfinished
    ended_s: float = math.nan
    prompt: object = None        # the token ids sent
    request: object = None       # the engine's Request (for t_depart_ns)
    trail: Optional[list] = None  # Request.trail, where the program has
    # one: the pass, within the request, that committed each token


def percentile(values, q: float) -> Optional[float]:
    """The q-quantile (0..1) by linear interpolation between order
    statistics; None of nothing."""
    v = sorted(values)
    if not v:
        return None
    pos = q * (len(v) - 1)
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def window_gaps(records, t0: float, t1: float) -> list[float]:
    """Every gap between consecutive tokens of one stream whose later
    token was delivered in [t0, t1): all of them, zeros included."""
    gaps = []
    for r in records:
        s = r.stamps
        for a, b in zip(s, s[1:]):
            if t0 <= b < t1:
                gaps.append(b - a)
    return gaps


def gap_percentile_ms(records, seconds: float, q: float) -> Optional[float]:
    """The q-quantile, in ms, of the gaps of the window [0, seconds)."""
    v = percentile(window_gaps(records, 0.0, seconds), q)
    return None if v is None else v * 1e3


def window_tokens(records, t0: float, t1: float) -> int:
    """Output tokens delivered in [t0, t1)."""
    return sum(1 for r in records for s in r.stamps if t0 <= s < t1)


def ttfts(records, give_up_s: float) -> list[float]:
    """First token minus due time of every request due in the window; one
    that never got a token waited until ``give_up_s`` (it misses every
    limit and still counts)."""
    out = []
    for r in records:
        if r.in_window:
            first = r.stamps[0] if r.stamps else give_up_s
            out.append(first - r.due_s)
    return out


def live_tokens_at(records, t: float) -> tuple[int, int]:
    """(streams decoding, cached tokens they hold) at time t: a stream is
    live from its first token to its last."""
    streams = tokens = 0
    for r in records:
        s = r.stamps
        if not s or s[0] > t or (r.status is not None and t > s[-1]):
            continue
        streams += 1
        tokens += r.prompt_len + sum(1 for x in s if x <= t)
    return streams, tokens
