"""Fenced phase timing — honest wall-clock measurement under JAX async
dispatch.

The bug this module exists to fix (ISSUE 9 satellite): the serving
loops wrapped jitted calls in bare ``perf_counter`` pairs.  JAX
dispatches asynchronously, so such a pair measures how long it took to
*enqueue* the computation, not to run it — the recorded "phase time"
was dispatch time misattributed as execution time, and the error grows
exactly when the pipeline is healthiest (deep async queues).

:class:`FencedTimer` makes the choice explicit.  ``fence=False``
measures dispatch time and says so (``fenced`` stays False on the
result); ``fence=True`` calls ``jax.block_until_ready`` on the values
handed to :meth:`fence` before closing the clock, which measures real
execution time *at the cost of serializing the pipeline* — the fence
itself is a host sync the unfenced run would not pay, so fenced
numbers are exact per-phase but pessimistic end-to-end
(docs/observability.md "Fencing").  The scheduler maps its
``sync_per_step`` flag onto the fence, which is why per-tick stats are
documented as exact under ``sync_per_step`` and dispatch-time
otherwise.
"""
from __future__ import annotations

import time


class FencedTimer:
    """``with FencedTimer(fence=...) as t: y = step(); t.fence(y)``.

    After exit, ``elapsed_s`` is the measured wall time and ``fenced``
    records whether a ``block_until_ready`` closed the clock (False
    means the number is dispatch time).  ``synced`` counts the host
    syncs the fence actually performed — the scheduler adds it to its
    ``host_syncs`` accounting so the fence's cost is visible, never
    silent — and ``fence_s`` is the wall time spent inside them (the
    host waiting on the device)."""

    __slots__ = ("fence_enabled", "fenced", "synced", "elapsed_s",
                 "fence_s", "_t0")

    def __init__(self, *, fence: bool = False):
        self.fence_enabled = fence
        self.fenced = False
        self.synced = 0
        self.elapsed_s = 0.0
        self.fence_s = 0.0
        self._t0 = 0.0

    def __enter__(self) -> "FencedTimer":
        self._t0 = time.perf_counter()
        return self

    def fence(self, *values) -> None:
        """Block until ``values`` are materialized — only when the timer
        was built with ``fence=True`` (so call sites can hand the result
        over unconditionally and let the timer own the decision)."""
        if self.fence_enabled:
            import jax
            t0 = time.perf_counter()
            jax.block_until_ready(values)
            self.fence_s += time.perf_counter() - t0
            self.fenced = True
            self.synced += 1

    def __exit__(self, *exc) -> bool:
        self.elapsed_s = time.perf_counter() - self._t0
        return False


def measure(fn, *, fence: bool = True, repeats: int = 1) -> float:
    """Best-of-``repeats`` wall seconds for ``fn()``, fencing the result
    when asked — the obs-layer primitive tests and the overhead
    benchmark share."""
    best = float("inf")
    for _ in range(max(1, repeats)):
        with FencedTimer(fence=fence) as t:
            y = fn()
            t.fence(y)
        best = min(best, t.elapsed_s)
    return best
