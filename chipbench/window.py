"""What the window saw, on the host clock, and the arithmetic over it.

``Recorder`` turns the scheduler's audit events into timestamps: each
event is stamped with the time ``step()`` returned, which under
``sync_per_step`` is after the device finished the step's work (a
streaming server could send the token then).  The end-to-end metrics are
plain functions of the record:

* a rate is all the work stamped inside the window over the window's
  whole length;
* a tail is taken over every request (time to first token, from the
  request's due time) or every gap between two tokens of one request
  that ends inside the window.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass
class Req:
    rid: int
    due: float
    prompt_len: int
    max_new: int
    submitted: float
    first: float | None = None
    emissions: list = dataclasses.field(default_factory=list)
    done: float | None = None
    failed: bool = False


@dataclasses.dataclass
class Dispatch:
    t: float
    kind: str             # "prefill" | "decode"
    width: int = 0        # prefill: rows run (the padded chunk)
    start: int = 0        # prefill: prompt positions [start, end)
    end: int = 0
    contexts: tuple = ()  # decode: keys each active slot's token reads


class Recorder:
    """Host-clock record of one run; ``width_of(rem)`` is the prefill
    width the scheduler runs for ``rem`` remaining prompt tokens."""

    def __init__(self, width_of):
        self.width_of = width_of
        self.reqs: dict[int, Req] = {}
        self.prefill_tokens: list = []   # (t, tokens)
        self.dispatches: list = []
        self.w0 = self.w1 = None

    def submitted(self, rid, *, due, t, prompt_len, max_new):
        self.reqs[rid] = Req(rid, due, prompt_len, max_new, t)

    def observe(self, events, t: float) -> None:
        for ev in events:
            kind = ev[0]
            if kind == "prefill":
                _, rid, _slot, start, end = ev
                r = self.reqs[rid]
                self.prefill_tokens.append((t, end - start))
                self.dispatches.append(Dispatch(
                    t, "prefill", width=self.width_of(r.prompt_len - start),
                    start=start, end=end))
                if end == r.prompt_len:
                    r.first = t
                    r.emissions.append(t)
            elif kind == "decode":
                ctx = []
                for rid in ev[1]:
                    r = self.reqs[rid]
                    ctx.append(r.prompt_len + len(r.emissions))
                    r.emissions.append(t)
                self.dispatches.append(Dispatch(t, "decode",
                                                contexts=tuple(ctx)))
            elif kind == "finish":
                self.reqs[ev[1]].done = t
            elif kind in ("evict", "reject"):
                self.reqs[ev[1]].failed = True

    def backlog(self) -> int:
        """Requests submitted and not yet finished or failed."""
        return sum(1 for r in self.reqs.values()
                   if r.done is None and not r.failed)

    # ------------------------------------------------------------ window
    def inside(self, t) -> bool:
        return t is not None and self.w0 < t <= self.w1

    @property
    def length(self) -> float:
        return self.w1 - self.w0

    def due_in_window(self) -> list:
        return [r for r in self.reqs.values() if self.w0 <= r.due < self.w1]


def prompt_tok_s(rec: Recorder) -> float:
    return sum(n for t, n in rec.prefill_tokens if rec.inside(t)) \
        / rec.length


def out_tok_s(rec: Recorder) -> float:
    return sum(1 for r in rec.reqs.values() for t in r.emissions
               if rec.inside(t)) / rec.length


def ttft_samples(rec: Recorder) -> list:
    """Due time to first token of every request due inside the window;
    a request that never got its first token reads infinite."""
    return [(r.first - r.due) if r.first is not None else math.inf
            for r in rec.due_in_window()]


def itl_samples(rec: Recorder) -> list:
    """Every gap between consecutive tokens of one request that ends
    inside the window, all requests pooled."""
    out = []
    for r in rec.reqs.values():
        e = r.emissions
        out.extend(b - a for a, b in zip(e, e[1:]) if rec.inside(b))
    return out


def percentile(values, q: float) -> float | None:
    if not values:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))
