"""Continuous-batching serving gates.

The serving analogue of the paper's bit-exactness protocol: for a fixed
request set, the continuous-batching ``serve`` must return
token-for-token identical outputs to per-request greedy ``generate``,
across batch_slots in {1, 2, 4} and mixed prompt lengths — plus the
scheduler invariants (slot exclusivity, exactly-once completion, FIFO
admission, no freed-page aliasing) and the plans-stay-hot property
(``plan_cache_info().misses`` flat after the first refill cycle).
"""
import dataclasses

import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:      # dev extra (requirements-dev.txt)
    from _hypothesis_stub import given, settings, st

import jax.numpy as jnp

from repro import gemm
from repro.models import model_zoo
from repro.runtime.batching import ContinuousBatchingScheduler
from repro.runtime.serve_loop import Engine

MAX_LEN = 48
PAGE = 8
CHUNK = 8
# mixed prompt lengths: < chunk, == chunk, ragged tails, near max
LENS = [5, 17, 8, 23, 3, 12]
MNS = [6, 3, 8, 4, 5, 7]


def _requests(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, l).astype(np.int32)
            for l in lens]


def _refs(eng, reqs, mns):
    return [np.asarray(eng.generate(jnp.asarray(r)[None], m)[0][0])
            for r, m in zip(reqs, mns)]


@pytest.fixture(scope="module")
def stablelm():
    cfg = model_zoo.reduced_config(model_zoo.get_config("stablelm-3b"))
    params = model_zoo.build(cfg)
    return cfg, Engine(cfg, params, max_len=MAX_LEN, packed=False)


@pytest.fixture(scope="module")
def stablelm_packed():
    cfg = model_zoo.reduced_config(model_zoo.get_config("stablelm-3b"))
    params = model_zoo.build(cfg)
    return cfg, Engine(cfg, params, max_len=MAX_LEN, packed=True)


# ----------------------------------------------------------- parity gate
@pytest.mark.parametrize("megastep", [1, 4])
@pytest.mark.parametrize("batch_slots", [1, 2, 4])
def test_parity_vs_per_request_generate(stablelm, batch_slots, megastep):
    """Bit-identical to per-request generate at every megastep depth:
    a D-deep megastep drain runs the SAME jitted tick D times device-
    side, so fusing the loop must not move a single bit."""
    cfg, eng = stablelm
    reqs = _requests(cfg, LENS)
    refs = _refs(eng, reqs, MNS)
    outs, stats = eng.serve(reqs, batch_slots=batch_slots,
                            max_new_tokens=MNS, prefill_chunk=CHUNK,
                            page_size=PAGE, check_invariants=True,
                            megastep_depth=megastep)
    for i, (o, r) in enumerate(zip(outs, refs)):
        np.testing.assert_array_equal(
            o, r, err_msg=f"request {i} diverged at batch_slots="
                          f"{batch_slots}, megastep={megastep}")
    assert stats.prefill_tokens == sum(LENS)
    assert stats.decode_tokens == sum(MNS)
    assert stats.decode_ticks == len(stats.decode_tick_ms)
    if megastep > 1:
        # the dispatch collapse actually happened
        assert stats.decode_dispatches < stats.decode_ticks
    else:
        assert stats.decode_dispatches == stats.decode_ticks


@pytest.mark.parametrize("megastep", [1, 4])
def test_parity_packed_engine(stablelm_packed, megastep):
    """The packed (plan/execute) path — decode plans through the decode
    lane — must satisfy the same gate at every megastep depth."""
    cfg, eng = stablelm_packed
    reqs = _requests(cfg, LENS[:4])
    refs = _refs(eng, reqs, MNS[:4])
    outs, _ = eng.serve(reqs, batch_slots=2, max_new_tokens=MNS[:4],
                        prefill_chunk=CHUNK, page_size=PAGE,
                        megastep_depth=megastep)
    for o, r in zip(outs, refs):
        np.testing.assert_array_equal(o, r)


def test_parity_softcap_window_arch():
    """gemma2: logit softcap + alternating local/global windows."""
    cfg = model_zoo.reduced_config(model_zoo.get_config("gemma2-9b"))
    eng = Engine(cfg, model_zoo.build(cfg), max_len=MAX_LEN, packed=False)
    reqs = _requests(cfg, [5, 20, 11], seed=1)
    mns = [4, 6, 3]
    refs = _refs(eng, reqs, mns)
    outs, _ = eng.serve(reqs, batch_slots=2, max_new_tokens=mns,
                        prefill_chunk=CHUNK, page_size=PAGE)
    for o, r in zip(outs, refs):
        np.testing.assert_array_equal(o, r)


def test_parity_lane_wide_heads():
    """Heads of 128 keep their own pool dim (kv_cache.leaf_specs_for
    merges narrower ones into one row): the same gate on that layout."""
    cfg = dataclasses.replace(
        model_zoo.reduced_config(model_zoo.get_config("stablelm-3b")),
        head_dim=128)
    eng = Engine(cfg, model_zoo.build(cfg), max_len=MAX_LEN, packed=False)
    reqs = _requests(cfg, [5, 20, 11], seed=4)
    mns = [4, 6, 3]
    refs = _refs(eng, reqs, mns)
    outs, _ = eng.serve(reqs, batch_slots=2, max_new_tokens=mns,
                        prefill_chunk=CHUNK, page_size=PAGE)
    for o, r in zip(outs, refs):
        np.testing.assert_array_equal(o, r)


def test_parity_under_page_pressure(stablelm):
    """A pool smaller than the dense equivalent forces admission to wait
    for freed pages; outputs must not change."""
    cfg, eng = stablelm
    lens, mns = [20, 20, 20, 20], [8, 8, 8, 8]
    reqs = _requests(cfg, lens, seed=2)
    refs = _refs(eng, reqs, mns)
    # 4 slots x 6 pages dense-equivalent = 24; 9 admits at most two
    outs, stats = eng.serve(reqs, batch_slots=4, max_new_tokens=mns,
                            prefill_chunk=CHUNK, page_size=PAGE,
                            num_pages=9, check_invariants=True,
                            sync_per_step=True)
    for o, r in zip(outs, refs):
        np.testing.assert_array_equal(o, r)
    assert max(r.queue_wait_s for r in stats.requests) > 0


# ------------------------------------------------------ plans stay hot
def test_plan_misses_flat_after_first_refill_cycle(stablelm_packed):
    cfg, eng = stablelm_packed
    reqs = _requests(cfg, LENS, seed=3)
    eng.serve(reqs, batch_slots=2, max_new_tokens=MNS,
              prefill_chunk=CHUNK, page_size=PAGE)
    misses = gemm.plan_cache_info().misses
    # fresh mixed lengths, several refill cycles — same static shapes
    reqs2 = _requests(cfg, [7, 19, 2, 11, 23, 4], seed=4)
    eng.serve(reqs2, batch_slots=2, max_new_tokens=[3, 5, 2, 6, 4, 3],
              prefill_chunk=CHUNK, page_size=PAGE)
    assert gemm.plan_cache_info().misses == misses, \
        "steady-state serving replanned a GEMM"


def test_bucket_m_plan_key_stability():
    """Ragged chunk row counts inside one bucket share one plan key."""
    assert [gemm.bucket_m(m) for m in (1, 8, 9, 16, 33, 64, 65, 129)] \
        == [8, 8, 16, 16, 64, 64, 128, 256]
    with pytest.raises(ValueError):
        gemm.bucket_m(0)
    gemm.plan_cache_clear()
    for m in (17, 20, 31, 32):           # all bucket to 32
        gemm.plan(gemm.bucket_m(m), 64, 256)
    assert gemm.plan_cache_info().misses == 1


# ------------------------------------------------- scheduler invariants
def _audit_trace(trace, n_requests):
    """Replay the scheduler's event log against the serving invariants."""
    active = {}                          # slot -> rid
    admitted, finished = [], []
    for ev in trace:
        if ev[0] == "admit":
            rid, slot = ev[1], ev[2]
            assert slot not in active, \
                f"slot {slot} admitted {rid} while serving {active[slot]}"
            active[slot] = rid
            admitted.append(rid)
        elif ev[0] == "decode":
            assert all(r in active.values() for r in ev[1]), \
                "decoded a request not assigned to any slot"
        elif ev[0] == "finish":
            rid, slot = ev[1], ev[2]
            assert active.get(slot) == rid
            del active[slot]
            finished.append(rid)
    assert not active, f"requests never finished: {active}"
    assert admitted == sorted(admitted), "FIFO admission order broken"
    assert sorted(finished) == list(range(n_requests)), \
        "each request must complete exactly once"


class FakeEngine:
    """Duck-typed engine: scheduling logic only, no tracing — lets the
    invariant property run thousands of schedules cheaply."""

    def __init__(self, cfg, max_len):
        self.cfg = cfg
        self.max_len = max_len

    def prefill_chunk(self, pages, pt, lens, tokens, logit_index, *,
                      page_size):
        return jnp.zeros((), jnp.int32), pages

    def decode_step(self, pages, pt, lens, mask, last, *, page_size):
        return last, pages


def _fake_cfg():
    return model_zoo.reduced_config(model_zoo.get_config("stablelm-3b"))


def _run_schedule(cfg, lens, mns, *, batch_slots, num_pages=None):
    sched = ContinuousBatchingScheduler(
        FakeEngine(cfg, MAX_LEN), batch_slots=batch_slots,
        prefill_chunk=CHUNK, page_size=PAGE, num_pages=num_pages,
        check_invariants=True)
    reqs = _requests(cfg, lens, seed=7)
    outs, stats = sched.run(reqs, mns)
    _audit_trace(sched.trace, len(lens))
    assert [len(o) for o in outs] == list(mns)
    assert stats.prefill_tokens == sum(lens)
    assert stats.decode_tokens == sum(mns)
    sched.kv.check_no_aliasing()
    assert sched.kv.free_count == sched.kv.num_pages, "pages leaked"
    return sched


def test_scheduler_invariants_deterministic():
    cfg = _fake_cfg()
    for slots in (1, 2, 4):
        _run_schedule(cfg, LENS, MNS, batch_slots=slots)
    # pressure: at most one live request's worth of pages
    _run_schedule(cfg, [20, 20, 20], [8, 8, 8], batch_slots=3,
                  num_pages=5)


def test_real_engine_trace_invariants(stablelm):
    cfg, eng = stablelm
    sched = ContinuousBatchingScheduler(
        eng, batch_slots=2, prefill_chunk=CHUNK, page_size=PAGE,
        check_invariants=True)
    sched.run(_requests(cfg, LENS), MNS)
    _audit_trace(sched.trace, len(LENS))


@settings(max_examples=40, deadline=None)
@given(lens=st.lists(st.integers(1, 30), min_size=1, max_size=10),
       seed=st.integers(0, 2 ** 16),
       batch_slots=st.integers(1, 5),
       tight=st.booleans())
def test_scheduler_invariants_property(lens, seed, batch_slots, tight):
    """No slot serves two requests at once, every request completes
    exactly once, FIFO admission holds, freed pages never alias — for
    arbitrary request mixes, pool widths, and page pressure."""
    rng = np.random.default_rng(seed)
    mns = [int(rng.integers(1, min(12, MAX_LEN - l + 1) + 1))
           for l in lens]
    need_max = max(-(-(l + m - 1) // PAGE) for l, m in zip(lens, mns))
    num_pages = None
    if tight:      # smallest pool that can still admit the largest req
        num_pages = max(need_max, 2)
    _run_schedule(_fake_cfg(), lens, mns, batch_slots=batch_slots,
                  num_pages=num_pages)


# --------------------------------------------------- stats + guard rails
def test_genstats_generate_counts_emitted_tokens(stablelm):
    """GenStats bug fix: generate emits max_new tokens per row and the
    stats must say so (not b * (max_new - 1))."""
    cfg, eng = stablelm
    prompts = jnp.asarray(_requests(cfg, [6, 6, 6])[0])[None]
    prompts = jnp.tile(prompts, (3, 1))
    _, stats = eng.generate(prompts, 5)
    assert stats.decode_tokens == 3 * 5
    assert stats.prefill_tokens == 3 * 6


def test_serve_rejects_oversized_request(stablelm):
    cfg, eng = stablelm
    with pytest.raises(ValueError):
        eng.serve(_requests(cfg, [MAX_LEN]), batch_slots=2,
                  max_new_tokens=8, page_size=PAGE)
    with pytest.raises(ValueError):
        eng.serve([np.zeros((0,), np.int32)], batch_slots=2,
                  max_new_tokens=2, page_size=PAGE)


def test_serve_stats_latency_fields(stablelm):
    cfg, eng = stablelm
    reqs = _requests(cfg, LENS[:3], seed=6)
    _, stats = eng.serve(reqs, batch_slots=2, max_new_tokens=3,
                         prefill_chunk=CHUNK, page_size=PAGE,
                         sync_per_step=True)
    assert len(stats.requests) == 3
    for r in stats.requests:
        assert r.ttft_s >= r.queue_wait_s >= 0
        assert r.total_s >= r.ttft_s
        assert r.decode_tps > 0
    assert stats.percentile("ttft_s", 95) >= stats.percentile("ttft_s", 5)
    assert stats.wall_s > 0 and stats.total_tps > 0


def test_serve_stats_phase_breakdown(stablelm):
    """Per-phase tick latency + host-sync accounting (decode lane
    observability satellite)."""
    cfg, eng = stablelm
    reqs = _requests(cfg, LENS[:3], seed=8)
    _, stats = eng.serve(reqs, batch_slots=2, max_new_tokens=5,
                         prefill_chunk=CHUNK, page_size=PAGE,
                         sync_per_step=True, megastep_depth=2)
    assert stats.megastep_depth == 2
    assert len(stats.prefill_tick_ms) > 0
    assert stats.decode_ticks > 0
    assert stats.phase_percentile("decode", 99) >= \
        stats.phase_percentile("decode", 50) > 0
    assert stats.phase_percentile("prefill", 50) > 0
    # sync_per_step: one blocking sync per device dispatch + the final
    # materialize
    assert stats.host_syncs == (len(stats.prefill_tick_ms)
                                + stats.decode_dispatches + 1)
    # async run: only the end-of-run materialize blocks
    _, astats = eng.serve(_requests(cfg, LENS[:2], seed=9),
                          batch_slots=2, max_new_tokens=3,
                          prefill_chunk=CHUNK, page_size=PAGE)
    assert astats.host_syncs == 1


def test_megastep_under_page_pressure(stablelm):
    """Deep megasteps pre-allocate D tokens of pages per drain; the
    reservation-based admission must stay deadlock-free and parity must
    hold when the pool is tight."""
    cfg, eng = stablelm
    lens, mns = [20, 20, 20, 20], [8, 8, 8, 8]
    reqs = _requests(cfg, lens, seed=2)
    refs = _refs(eng, reqs, mns)
    outs, stats = eng.serve(reqs, batch_slots=4, max_new_tokens=mns,
                            prefill_chunk=CHUNK, page_size=PAGE,
                            num_pages=9, check_invariants=True,
                            megastep_depth=4)
    for o, r in zip(outs, refs):
        np.testing.assert_array_equal(o, r)


def test_megastep_never_overshoots_max_new(stablelm):
    """The realized drain depth caps at the smallest remaining budget:
    no slot generates past its max_new, so the trace finish events and
    emitted counts are exact at any depth."""
    cfg, eng = stablelm
    reqs = _requests(cfg, [5, 7], seed=10)
    sched = ContinuousBatchingScheduler(
        eng, batch_slots=2, prefill_chunk=CHUNK, page_size=PAGE,
        check_invariants=True, megastep_depth=8)
    outs, stats = sched.run(reqs, [3, 9])   # one budget far below D
    _audit_trace(sched.trace, 2)
    assert [len(o) for o in outs] == [3, 9]
    assert stats.decode_tokens == 12


def test_megastep_requires_capable_engine():
    cfg = _fake_cfg()
    with pytest.raises(ValueError, match="decode_megastep"):
        ContinuousBatchingScheduler(
            FakeEngine(cfg, MAX_LEN), batch_slots=2,
            prefill_chunk=CHUNK, page_size=PAGE, megastep_depth=4)
    with pytest.raises(ValueError):
        ContinuousBatchingScheduler(
            FakeEngine(cfg, MAX_LEN), batch_slots=2,
            prefill_chunk=CHUNK, page_size=PAGE, megastep_depth=0)


# ---------------------------------------------------- prefix cache gates
def _shared_prefix_reqs(cfg, groups, per_group, prefix_len, tail_lens,
                        seed=12):
    """``groups`` distinct shared preambles, ``per_group`` requests each
    (tails unique), interleaved by group so warm hits happen mid-run."""
    rng = np.random.default_rng(seed)
    prefixes = [rng.integers(1, cfg.vocab_size, prefix_len)
                .astype(np.int32) for _ in range(groups)]
    reqs = []
    for i in range(per_group):
        for p in prefixes:
            t = tail_lens[(i * groups) % len(tail_lens)] + len(reqs) % 3
            reqs.append(np.concatenate(
                [p, rng.integers(1, cfg.vocab_size, t).astype(np.int32)]))
    return reqs


def test_prefix_cache_parity_cold_warm_cow(stablelm):
    """The tentpole gate: serve with the cache on stays token-identical
    to per-request generate through cold admissions, warm full-page
    hits, AND mid-page COW forks (prefix_len 18 = 2 full pages + 2
    tokens into the divergence page at PAGE=8)."""
    cfg, eng = stablelm
    reqs = _shared_prefix_reqs(cfg, groups=1, per_group=4,
                               prefix_len=18, tail_lens=[6, 3, 5, 4])
    mns = [4, 3, 5, 2]
    refs = _refs(eng, reqs, mns)
    outs, stats = eng.serve(reqs, batch_slots=2, max_new_tokens=mns,
                            prefill_chunk=CHUNK, page_size=PAGE,
                            check_invariants=True, prefix_cache=True)
    for i, (o, r) in enumerate(zip(outs, refs)):
        np.testing.assert_array_equal(
            o, r, err_msg=f"request {i} diverged with prefix cache on")
    px = stats.prefix
    assert px.hits >= 1 and px.cow_forks >= 1
    # computed prefill shrank by exactly the reused positions
    assert stats.prefill_tokens == sum(len(r) for r in reqs) \
        - px.hit_tokens
    # cache off: same tokens, no counters
    outs_off, stats_off = eng.serve(reqs, batch_slots=2,
                                    max_new_tokens=mns,
                                    prefill_chunk=CHUNK, page_size=PAGE)
    assert stats_off.prefix is None
    for o, r in zip(outs_off, refs):
        np.testing.assert_array_equal(o, r)


def test_prefix_cache_parity_under_eviction_pressure(stablelm):
    """A tight pool forces the LRU evictor to reclaim cached pages
    mid-run; parity and the teardown leak audit must survive the
    churn."""
    cfg, eng = stablelm
    reqs = _shared_prefix_reqs(cfg, groups=4, per_group=2,
                               prefix_len=16, tail_lens=[4, 6, 5],
                               seed=13)
    mns = [4] * len(reqs)
    refs = _refs(eng, reqs, mns)
    outs, stats = eng.serve(reqs, batch_slots=2, max_new_tokens=mns,
                            prefill_chunk=CHUNK, page_size=PAGE,
                            num_pages=9, check_invariants=True,
                            prefix_cache=True)
    for i, (o, r) in enumerate(zip(outs, refs)):
        np.testing.assert_array_equal(
            o, r, err_msg=f"request {i} diverged under eviction")
    assert stats.prefix.evicted_pages > 0, \
        "tight pool never pressured the cache — gate unexercised"


def test_prefix_hits_after_warmup_resolve_no_plans(stablelm_packed):
    """A prefix hit starts prefill mid-prompt, so the divergent remainder
    dispatches at a chunk-tail width; ``warmup_plans`` covers every such
    width, so serving with the cache on resolves no new plan, and its
    tokens equal the cache-off serve's and per-request ``generate``'s."""
    cfg, eng = stablelm_packed
    chunk = 16
    reqs = _shared_prefix_reqs(cfg, groups=2, per_group=3, prefix_len=18,
                               tail_lens=[5, 2, 7], seed=15)
    mns = [3] * len(reqs)
    refs = _refs(eng, reqs, mns)
    eng.warmup_plans(batch_slots=2, prefill_chunk=chunk, page_size=PAGE)
    misses = gemm.plan_cache_info().misses
    outs, stats = eng.serve(reqs, batch_slots=2, max_new_tokens=mns,
                            prefill_chunk=chunk, page_size=PAGE,
                            prefix_cache=True)
    assert gemm.plan_cache_info().misses == misses, \
        "a prefix-hit remainder resolved a plan warmup should have owned"
    assert stats.prefix.hits >= 1
    outs_off, _ = eng.serve(reqs, batch_slots=2, max_new_tokens=mns,
                            prefill_chunk=chunk, page_size=PAGE)
    for i, (o, o_off, r) in enumerate(zip(outs, outs_off, refs)):
        np.testing.assert_array_equal(o, o_off, err_msg=f"request {i}")
        np.testing.assert_array_equal(o, r, err_msg=f"request {i}")


@pytest.mark.slow
def test_prefix_cache_parity_quantized():
    """Cached KV written by a quantized (int8 packs) prefill is reused
    bit-identically — the cache composes with the quantized serving
    contract."""
    cfg = model_zoo.reduced_config(model_zoo.get_config("stablelm-3b"))
    eng = Engine(cfg, model_zoo.build(cfg), max_len=MAX_LEN, packed=True,
                 quant="int8")
    reqs = _shared_prefix_reqs(cfg, groups=1, per_group=3,
                               prefix_len=18, tail_lens=[5, 3, 6],
                               seed=14)
    mns = [4, 3, 5]
    refs = _refs(eng, reqs, mns)
    outs, stats = eng.serve(reqs, batch_slots=2, max_new_tokens=mns,
                            prefill_chunk=CHUNK, page_size=PAGE,
                            prefix_cache=True)
    for o, r in zip(outs, refs):
        np.testing.assert_array_equal(o, r)
    assert stats.prefix.hits >= 1 and stats.quant == "int8"


@pytest.mark.slow
def test_parity_quantized_megastep():
    """Quantized decode through the lane (split-K plans on quant packs)
    stays bit-identical to per-request generate across megastep depth."""
    cfg = model_zoo.reduced_config(model_zoo.get_config("stablelm-3b"))
    eng = Engine(cfg, model_zoo.build(cfg), max_len=MAX_LEN, packed=True,
                 quant="int8")
    reqs = _requests(cfg, [5, 17, 8], seed=11)
    mns = [4, 6, 3]
    refs = _refs(eng, reqs, mns)
    for depth in (1, 4):
        outs, stats = eng.serve(reqs, batch_slots=2, max_new_tokens=mns,
                                prefill_chunk=CHUNK, page_size=PAGE,
                                megastep_depth=depth)
        for o, r in zip(outs, refs):
            np.testing.assert_array_equal(o, r)
        assert stats.quant == "int8"
