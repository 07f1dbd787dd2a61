"""The one load generator: a traffic mix and a seed give a request schedule.

Every seed gets the same set of sizes and arrival gaps, in its own order.
Prompt and output lengths are the (i + 0.5)/n quantiles of the mix's
distributions, and the gaps between arrivals those of the exponential
distribution at the mix's rate, within each segment (ramp, window,
after). The seed draws three offsets, one for the gaps, one for the
prompt lengths and one for the output lengths; arrival i takes the value
whose rank is that of frac(offset + i * step) among the segment's n,
with an irrational step of its own for each (a Kronecker sequence). So
each seed's order is its own, and each stretch of a window carries about
the window's share of arrivals and of work: long gaps, or long prompts,
never bunch as they do in a free permutation, which at four fifths of the
knee moves the time to first token's 90th percentile by a factor of two
from seed to seed. The seed also draws the token ids.

Arrival kinds:

``poisson``  open loop at ``rate_per_s``; the schedule runs ``ramp_s``
             before the window and ``drain_s`` after it.
``backlog``  the queue never drains: the driver keeps
             ``waiting_per_slot * max_slots`` requests waiting, drawing
             them from the stream in blocks of ``BACKLOG_BLOCK``. When
             the window closes, the requests still waiting are withdrawn
             and those in flight drain for at most ``drain_s``.

``long`` (optional) gives every ``every``-th arrival of a segment a
prompt drawn from its own length distribution: round(n / every) of the
segment's n arrivals, ``every`` apart, from an offset drawn from the
seed.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np

BACKLOG_BLOCK = 64
# the irrational steps of the three orders (golden ratio, sqrt 2, sqrt 3)
STEP_GAP = (5 ** 0.5 - 1) / 2
STEP_PROMPT = 2 ** 0.5 - 1
STEP_OUTPUT = 3 ** 0.5 - 1


@dataclass
class Arrival:
    rid: int
    due_s: float | None    # from the start of the schedule; None: backlog
    prompt: list
    max_new: int
    phase: str             # "ramp" | "window" | "after" | "backlog"


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent numpy streams from one (possibly large) seed."""
    return np.random.default_rng([seed % (1 << 64), stream])


def quantiles(dist: dict, n: int) -> np.ndarray:
    """The (i + 0.5)/n quantiles of a length distribution, as ints."""
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        x = dist["median"] * np.exp(dist["sigma"] * z)
    elif kind == "uniform":
        x = dist["min"] + u * (dist["max"] - dist["min"])
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(int)


def spread_order(n: int, offset: float, step: float) -> np.ndarray:
    """The ranks of frac(offset + i * step), i < n: index a sorted array
    with it to put its values in an order that spreads them evenly."""
    x = (offset + np.arange(n) * step) % 1.0
    return np.argsort(np.argsort(x, kind="stable"), kind="stable")


def exp_gaps(n: int, length_s: float) -> np.ndarray:
    """Exponential quantile gaps scaled to sum to ``length_s``."""
    u = (np.arange(n) + 0.5) / n
    g = -np.log1p(-u)
    return g * (length_s / g.sum())


class Schedule:
    """The arrivals of one run: ``timed`` (open loop, sorted by due time)
    or drawn one by one from ``next_backlog`` (backlog mixes)."""

    def __init__(self, mix: dict, seed: int, seconds: float, vocab: int,
                 max_slots: int):
        self.mix = mix
        self.vocab = vocab
        self.rng = rng_for(seed, 1)        # the order of sizes and gaps
        self.tokens = rng_for(seed, 2)     # token ids
        arr = mix["arrival"]
        self.kind = arr["kind"]
        self.ramp_s = float(mix["ramp_s"])
        self.seconds = float(seconds)
        self.drain_s = float(mix["drain_s"])
        self._rid = 0
        self.timed: list[Arrival] = []
        self._backlog: list[Arrival] = []
        if self.kind == "poisson":
            rate = float(arr["rate_per_s"])
            start = 0.0
            for phase, length in (("ramp", self.ramp_s),
                                  ("window", self.seconds),
                                  ("after", self.drain_s)):
                n = int(round(rate * length))
                if n:
                    g = exp_gaps(n, length)[spread_order(
                        n, self.rng.random(), STEP_GAP)]
                    due = start + np.concatenate([[0.0], np.cumsum(g)[:-1]])
                    self.timed += self._draw(n, phase, due)
                start += length
        elif self.kind == "backlog":
            self.waiting_target = int(arr["waiting_per_slot"]) * max_slots
        else:
            raise ValueError(f"unknown arrival kind {self.kind!r}")

    def _draw(self, n: int, phase: str, due) -> list[Arrival]:
        rng, mix = self.rng, self.mix
        long = mix.get("long")
        count = int(round(n / long["every"])) if long else 0
        idx = []
        if count:
            every = long["every"]
            first = int(rng.integers(max(1, n - (count - 1) * every)))
            idx = [first + k * every for k in range(count)
                   if first + k * every < n]
        prompts = np.zeros(n, int)
        short = np.setdiff1d(np.arange(n), idx)
        prompts[short] = quantiles(mix["prompt_tokens"], len(short))[
            spread_order(len(short), rng.random(), STEP_PROMPT)]
        outs = quantiles(mix["output_tokens"], n)[
            spread_order(n, rng.random(), STEP_OUTPUT)]
        if idx:
            prompts[idx] = quantiles(long["prompt_tokens"], len(idx))[
                spread_order(len(idx), rng.random(), STEP_PROMPT)]
        out = []
        for i in range(n):
            out.append(Arrival(
                rid=self._rid + i,
                due_s=None if due is None else float(due[i]),
                prompt=self.tokens.integers(0, self.vocab,
                                            int(prompts[i])).tolist(),
                max_new=int(outs[i]), phase=phase))
        self._rid += n
        return out

    def next_backlog(self) -> Arrival:
        if not self._backlog:
            self._backlog = self._draw(BACKLOG_BLOCK, "backlog", None)[::-1]
        return self._backlog.pop()

    def max_tokens(self) -> int:
        """The longest prompt + output this mix can draw."""
        long = self.mix.get("long")
        p = self.mix["prompt_tokens"]["max"]
        if long:
            p = max(p, long["prompt_tokens"]["max"])
        return p + self.mix["output_tokens"]["max"]


def ceil_to(x: int, m: int) -> int:
    return int(math.ceil(x / m) * m)
