"""The one place the benchmark reads the scheduler's non-public state.

The window drives ``ContinuousBatchingScheduler.submit`` and ``.step``
as a streaming server would; what it needs beyond that (which tokens
each step emitted, and the served tokens themselves) is read here, so a
refactor of ``runtime/batching.py`` breaks this file alone.
"""
from __future__ import annotations


def events_since(sched, seen: int) -> tuple[list, int]:
    """Audit events appended since ``seen`` events in all; returns
    (new events, the new total).  The scheduler's log is bounded and
    drops its oldest events, so the total counts the dropped ones too."""
    log = sched.trace
    total = len(log) + log.dropped
    n = total - seen
    if n > len(log):
        raise RuntimeError(f"{n - len(log)} scheduler events were dropped "
                           f"before they were read")
    return [log[len(log) - n + j] for j in range(n)], total


def served_tokens(sched) -> dict:
    """rid -> int32 tokens of every request that finished DONE (one
    transfer of the device-side token history)."""
    sched._materialize()
    return {rid: oc.tokens for rid, oc in sched.outcomes.items()
            if oc.state.value == "DONE" and oc.tokens is not None}


def release(sched) -> None:
    """Drop the scheduler's device buffers (KV pool, token history) so
    the reference that follows has the chip's memory."""
    sched.kv.pages = None
    sched._history.clear()
    sched._pending.clear()
    sched._last = None
    for sl in sched.slots:
        sl.first_tok = None


def busy(sched) -> bool:
    """Whether a ``step()`` has work: a queued request or a live slot."""
    return bool(sched.queue) or any(sl.request is not None
                                    for sl in sched.slots)


def step_hlo(eng, *, slots: int, chunk: int, page_size: int,
             num_pages: int) -> dict:
    """Compiled HLO text of the full-width prefill step and the decode
    step the window dispatches (the persistent cache holds both)."""
    import jax
    import jax.numpy as jnp
    from repro import gemm
    from repro.runtime import kv_cache as KV
    sds, i32 = jax.ShapeDtypeStruct, jnp.int32
    cfg = eng.cfg
    pps = eng.max_len // page_size
    pages = {name: sds((cfg.num_layers, num_pages, page_size, *feat), dt)
             for name, (feat, dt) in KV.leaf_specs_for(cfg).items()}
    steps = eng._paged_steps(fallback=False)
    prefill = steps["prefill"].lower(
        eng.params, pages, sds((1, pps), i32), sds((1,), i32),
        sds((1, gemm.bucket_m(chunk)), i32), sds((), i32),
        page_size=page_size).compile().as_text()
    decode = steps["decode"].lower(
        eng.params, pages, sds((slots, pps), i32), sds((slots,), i32),
        sds((slots,), bool), sds((slots,), i32),
        page_size=page_size).compile().as_text()
    return {"prefill": prefill, "decode": decode}
