"""Shared benchmark protocol — the paper's §4.1 discipline, scaled to CPU.

Paper: median over eleven isolated invocations; within an invocation
median over >= fifteen trials after warm-up.  Here (1-core CPU container)
the defaults shrink to reps×trials that finish in minutes, and every
table records the protocol it used.  Ratios are formed within one process
(like the paper's within-invocation ratios, so machine noise largely
cancels); absolute GFLOPS on this host are reported as context only —
the TPU-target numbers live in the §Roofline analysis, not here.
"""
from __future__ import annotations

import json
import os
import time

import jax
import numpy as np

from repro.launch.compile_cache import enable_compile_cache
from repro.obs import spans as _spans

# every benchmark imports this module before its first compile
enable_compile_cache()

OUT_DIR = os.path.join(os.path.dirname(__file__), "out")


def time_fn(fn, *args, trials: int = 5, warmup: int = 2) -> float:
    """Median seconds per call (blocked until ready)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(trials):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def gflops(m: int, n: int, k: int, seconds: float) -> float:
    return 2.0 * m * n * k / seconds / 1e9


def retry_on_noise(measure, accept, *, max_retries: int = 4):
    """The suite's retry-on-noise discipline (table8/table9, and the
    plan store's measured autotune), hoisted: when a row that should
    win by construction (the accepted mode does strictly less work)
    measures below threshold, that is timer noise — RE-MEASURE with
    more reps, never fudge the number.

    ``measure(extra_reps)`` produces a row (called first with 0);
    ``accept(row)`` says whether it cleared the threshold.  Each retry
    adds ``2 * tries`` reps.  Returns ``(row, tries)`` — the last row
    stands even if it never cleared, so a real regression still shows.
    """
    with _spans.span("retry_on_noise", max_retries=max_retries) as sp:
        with _spans.span("measure", extra_reps=0):
            row = measure(0)
        tries = 0
        while not accept(row) and tries < max_retries:
            tries += 1
            _spans.instant("noise_retry", tries=tries,
                           extra_reps=2 * tries)
            with _spans.span("measure", extra_reps=2 * tries):
                row = measure(2 * tries)
        sp.set(tries=tries, accepted=bool(accept(row)))
        return row, tries


def shared_prefix_trace(rng, *, requests: int, prompt_len: int, vocab: int,
                        share_ratio: float = 0.8, n_prefixes: int = 2,
                        prefix_frac=(0.5, 0.9)):
    """Seeded shared-prefix request trace — the prefix-cache workload.

    Production prompts open with shared preambles (system prompt,
    few-shot header); ``share_ratio`` of the requests here start with
    one of ``n_prefixes`` shared preambles whose lengths are drawn
    uniformly from ``prefix_frac`` of ``prompt_len``, then append a
    unique suffix (>= 1 token, so the final prompt position always
    differs and the last-token-recomputed cap is exercised) up to
    ``prompt_len`` tokens total.  The rest are fully unique prompts of
    random length.  Deterministic given ``rng``'s seed.

    Returns ``(reqs, info)``: the int32 prompt arrays (arrival order,
    shared/unique interleaved by the rng) and an info dict with the
    realized share — ``shared_requests``, ``shared_tokens`` (prompt
    positions covered by a preamble, the work an ideal cache deletes),
    ``total_tokens``, and ``prefix_lens``.
    """
    lo = max(1, int(prefix_frac[0] * prompt_len))
    hi = max(lo, int(prefix_frac[1] * prompt_len))
    prefixes = [rng.integers(1, vocab, int(rng.integers(lo, hi + 1)))
                .astype(np.int32) for _ in range(n_prefixes)]
    reqs, shared_reqs, shared_toks = [], 0, 0
    for _ in range(requests):
        if rng.random() < share_ratio:
            p = prefixes[int(rng.integers(n_prefixes))]
            sfx = rng.integers(1, vocab, int(rng.integers(
                1, prompt_len - len(p) + 1))).astype(np.int32)
            reqs.append(np.concatenate([p, sfx]))
            shared_reqs += 1
            shared_toks += len(p)
        else:
            reqs.append(rng.integers(1, vocab, int(rng.integers(
                4, prompt_len + 1))).astype(np.int32))
    return reqs, {
        "share_ratio": shared_reqs / max(requests, 1),
        "shared_requests": shared_reqs,
        "shared_tokens": int(shared_toks),
        "total_tokens": int(sum(len(r) for r in reqs)),
        "prefix_lens": [len(p) for p in prefixes],
    }


def write_table(name: str, rows: list[dict], *, meta: dict | None = None):
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, f"{name}.json"), "w") as f:
        json.dump({"meta": meta or {}, "rows": rows}, f, indent=1)


def print_csv(name: str, rows: list[dict]):
    if not rows:
        return
    cols = list(rows[0])
    print(f"# {name}")
    print(",".join(cols))
    for r in rows:
        print(",".join(_fmt(r[c]) for c in cols))


def _fmt(v) -> str:
    if isinstance(v, float):
        return f"{v:.6g}"
    return str(v)
