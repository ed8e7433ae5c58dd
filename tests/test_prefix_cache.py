"""Cross-request prefix cache gates: trie laws (longest match on page
boundaries, the last-token-recomputed cap, COW divergence detection,
insert idempotence), LRU eviction laws (refcount-0 only, cascade,
pinned pages survive), the refcount/COW page laws they ride on, and the
scheduler integration — duplicate-prefix schedules through a stub
engine must keep every existing invariant plus zero leaked pages."""
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:      # dev extra (requirements-dev.txt)
    from _hypothesis_stub import given, settings, st

import jax.numpy as jnp

from repro.runtime import kv_cache as KV
from repro.runtime.batching import ContinuousBatchingScheduler
from repro.runtime.prefix_cache import PrefixCache

PAGE = 4
MAX_LEN = 16
FEAT = (2,)


def _pool(num_slots=3, num_pages=None):
    return KV.PagedKVCache(
        num_layers=1, num_slots=num_slots, max_len=MAX_LEN,
        page_size=PAGE, leaf_specs={"pages_k": (FEAT, jnp.float32)},
        num_pages=num_pages)


def _prompt(*tokens):
    return np.asarray(tokens, np.int32)


def _complete(pool, cache, slot, tokens):
    """Simulate a finished prefill: alloc + set length, then index the
    prompt (what _prefill_step does on its final chunk)."""
    pool.alloc(slot, len(tokens))
    pool.lens[slot] = len(tokens)
    return cache.insert(slot, tokens)


# ------------------------------------------------------------- trie laws
def test_lookup_cold_is_a_miss():
    cache = PrefixCache(_pool())
    hit = cache.lookup(_prompt(1, 2, 3, 4, 5))
    assert hit.tokens == 0 and not hit.nodes and hit.fork_node is None


def test_identical_prompt_caps_at_last_token():
    """A verbatim re-ask still recomputes its final position — the
    logits there seed generation — so the second page is reused by COW
    fork, never shared outright."""
    pool, cache = _pool(), None
    cache = PrefixCache(pool)
    toks = _prompt(1, 2, 3, 4, 5, 6, 7, 8)
    _complete(pool, cache, 0, toks)
    hit = cache.lookup(toks)
    assert len(hit.nodes) == 1                 # page 0 shared whole
    assert hit.fork_node is not None           # page 1: COW, head only
    assert hit.fork_reuse == 3
    assert hit.tokens == 7 == len(toks) - 1


def test_longest_match_walks_page_boundaries():
    pool = _pool()
    cache = PrefixCache(pool)
    _complete(pool, cache, 0, _prompt(*range(1, 13)))       # 3 pages
    # shares 2 full pages, diverges at position 8
    hit = cache.lookup(_prompt(1, 2, 3, 4, 5, 6, 7, 8, 99, 98, 97))
    assert len(hit.nodes) == 2 and hit.fork_node is None
    assert hit.tokens == 8


def test_mid_page_divergence_is_a_cow_candidate():
    pool = _pool()
    cache = PrefixCache(pool)
    _complete(pool, cache, 0, _prompt(*range(1, 13)))
    # shares page 0 + two tokens of page 1
    hit = cache.lookup(_prompt(1, 2, 3, 4, 5, 6, 99, 98, 97, 96))
    assert len(hit.nodes) == 1
    assert hit.fork_node is not None and hit.fork_reuse == 2
    assert hit.tokens == 6


def test_sibling_runs_branch_like_a_radix_tree():
    pool = _pool()
    cache = PrefixCache(pool)
    _complete(pool, cache, 0, _prompt(1, 2, 3, 4, 10, 11, 12, 13))
    _complete(pool, cache, 1, _prompt(1, 2, 3, 4, 20, 21, 22, 23))
    assert cache.num_pages == 3                # shared head page once
    for tail, want in (((10, 11, 12, 13), 7), ((20, 21, 22, 23), 7)):
        hit = cache.lookup(_prompt(1, 2, 3, 4, *tail))
        assert hit.tokens == want              # own branch found
    # the deepest-sharing sibling wins the fork candidacy
    hit = cache.lookup(_prompt(1, 2, 3, 4, 20, 21, 99, 98))
    assert hit.fork_reuse == 2 and hit.tokens == 6


def test_insert_is_idempotent_and_keeps_first_page():
    pool = _pool()
    cache = PrefixCache(pool)
    toks = _prompt(1, 2, 3, 4, 5, 6, 7, 8)
    assert _complete(pool, cache, 0, toks) == 2
    first = [n.page for n in cache._walk()]
    # racing cold duplicate finishes in another slot: nothing re-indexed
    assert _complete(pool, cache, 1, toks) == 0
    assert sorted(n.page for n in cache._walk()) == sorted(first)
    assert cache.stats.inserted_pages == 2


def test_insert_never_indexes_a_page_twice():
    """A slot whose pages already index one prompt cannot index them
    again under another prompt's runs: the pages hold the first
    prompt's KV, and one page in two trie nodes would break the
    reclaimable count."""
    pool = _pool()
    cache = PrefixCache(pool)
    assert _complete(pool, cache, 0, _prompt(1, 2, 3, 4, 5, 6, 7, 8)) == 2
    assert _complete(pool, cache, 0, _prompt(9, 9, 9, 9, 8, 8, 8, 8)) == 0
    pages = [n.page for n in cache._walk()]
    assert len(pages) == len(set(pages)) == 2
    pool.free(0)
    assert pool.reclaimable_count() == 2
    assert cache.lookup(_prompt(9, 9, 9, 9, 8, 8, 8, 8, 7)).tokens == 0


def test_partial_final_page_never_indexed():
    pool = _pool()
    cache = PrefixCache(pool)
    assert _complete(pool, cache, 0, _prompt(1, 2, 3, 4, 5, 6)) == 1
    assert cache.num_pages == 1                # the 2-token tail stays private


# ----------------------------------------------------------- admit laws
def test_admit_shares_pages_and_forks_divergence():
    pool = _pool()
    cache = PrefixCache(pool)
    toks = _prompt(*range(1, 13))
    _complete(pool, cache, 0, toks)
    pool.free(0)                               # pages survive cached
    covered = cache.admit(1, _prompt(1, 2, 3, 4, 5, 6, 99, 98, 97, 96))
    assert covered == 6
    shared = int(pool.page_table[1, 0])
    forked = int(pool.page_table[1, 1])
    trie_pages = [n.page for n in cache._walk()]
    assert shared in trie_pages                # head page shared
    assert forked not in trie_pages            # fork page private
    assert pool.refcount[shared] == 1 and pool.refcount[forked] == 1
    assert cache.stats.cow_forks == 1 and cache.stats.hit_tokens == 6
    pool.check_no_aliasing()


def test_fork_copies_page_contents():
    pool = _pool()
    cache = PrefixCache(pool)
    rng = np.random.default_rng(0)
    vals = rng.standard_normal((1, 8, *FEAT)).astype(np.float32)
    pool.alloc(0, 8)
    pool.pages["pages_k"] = KV.paged_update(
        pool.pages["pages_k"], jnp.asarray(vals),
        pool.table_device([0]), pool.lens_device([0]), PAGE, 0)
    pool.lens[0] = 8
    cache.insert(0, _prompt(1, 2, 3, 4, 5, 6, 7, 8))
    pool.free(0)
    cache.admit(1, _prompt(1, 2, 3, 4, 5, 6, 7, 99, 98))
    view = np.asarray(KV.paged_gather(
        pool.pages["pages_k"], pool.table_device([1]), PAGE, 0))
    # shared page verbatim + the forked page's reused head
    np.testing.assert_array_equal(view[0, :7], vals[0, :7])


def test_admit_cold_prompt_returns_zero():
    pool = _pool()
    cache = PrefixCache(pool)
    assert cache.admit(0, _prompt(5, 6, 7, 8, 9)) == 0
    assert pool.held(0) == 0 and cache.stats.misses == 1


# --------------------------------------------------------- eviction laws
def test_eviction_is_lru_over_refcount0_leaves():
    pool = _pool(num_slots=2, num_pages=4)
    cache = PrefixCache(pool)
    a, b = _prompt(1, 2, 3, 4, 9), _prompt(5, 6, 7, 8, 9)
    for toks in (a, b):
        _complete(pool, cache, 0, toks)
        pool.free(0)
    cache.admit(0, a)                          # touch a: b is now LRU
    pool.free(0)
    page_b = cache.lookup(b).nodes[0].page if cache.lookup(b).nodes \
        else None
    pool.alloc(1, 12)                          # 3 pages: needs 1 eviction
    assert cache.stats.evicted_pages == 1
    assert cache.lookup(b).tokens == 0         # b evicted...
    assert cache.lookup(a).tokens == 4         # ...a survived
    assert page_b is not None
    pool.check_no_aliasing()


def test_live_shared_pages_never_evicted():
    pool = _pool(num_slots=2, num_pages=2)
    cache = PrefixCache(pool)
    toks = _prompt(1, 2, 3, 4, 9)
    _complete(pool, cache, 0, toks)            # 2 pages: 1 cached + tail
    pool.free(0)
    cache.admit(0, toks)                       # cached page now live
    with pytest.raises(KV.OutOfPagesError):
        pool.alloc(1, 8)                       # only a live page remains
    assert cache.stats.evicted_pages == 0
    assert cache.lookup(toks).tokens == 4      # index intact
    pool.check_no_aliasing()


def test_eviction_cascades_through_emptied_parents():
    pool = _pool(num_slots=2, num_pages=4)
    cache = PrefixCache(pool)
    _complete(pool, cache, 0, _prompt(*range(1, 13)))   # 3-page chain
    pool.free(0)
    assert cache.num_pages == 3
    pool.alloc(1, 12)                          # demand the whole pool
    assert cache.stats.evicted_pages >= 2      # leaf, then its parent
    pool.check_no_aliasing()


def test_clear_returns_idle_pages():
    pool = _pool()
    cache = PrefixCache(pool)
    _complete(pool, cache, 0, _prompt(*range(1, 9)))
    pool.free(0)
    assert pool.free_count < pool.num_pages
    assert cache.clear() == 2
    assert cache.num_pages == 0
    pool.assert_all_free()


def test_reclaimable_count_is_exact():
    pool = _pool()
    cache = PrefixCache(pool)
    toks = _prompt(*range(1, 9))
    _complete(pool, cache, 0, toks)
    assert pool.reclaimable_count() == 0       # cached pages still live
    pool.free(0)
    assert pool.reclaimable_count() == 2
    cache.admit(1, toks)                       # hit pins the shared page
    # page0 is live (shared); page1 was only COPIED by the COW fork, so
    # it returns to refcount 0 and stays reclaimable
    assert pool.reclaimable_count() == 1
    pool.free(1)
    assert pool.reclaimable_count() == 2
    assert pool.reclaimable_count(exclude=[
        n.page for n in cache._walk()]) == 0


# ------------------------------------------------------------- property
@settings(max_examples=40, deadline=None)
@given(ops=st.lists(st.tuples(st.integers(0, 3), st.integers(0, 2),
                              st.integers(0, 5)),
                    min_size=1, max_size=50),
       num_pages=st.integers(3, 10),
       seed=st.integers(0, 99))
def test_cache_pool_interleaving_property(ops, num_pages, seed):
    """Arbitrary admit/complete/free/pressure interleavings never alias,
    never leak, never double-free — and teardown always audits clean."""
    rng = np.random.default_rng(seed)
    pool = _pool(num_slots=3, num_pages=num_pages)
    cache = PrefixCache(pool)
    prompts = [rng.integers(1, 5, rng.integers(2, MAX_LEN - 3))
               .astype(np.int32) for _ in range(4)]
    lens = [0, 0, 0]
    for op, slot, arg in ops:
        toks = prompts[arg % len(prompts)]
        try:
            if op == 0 and lens[slot] == 0:        # admit w/ prefix
                lens[slot] = max(cache.admit(slot, toks), 1)
                pool.alloc(slot, min(len(toks), MAX_LEN))
                pool.lens[slot] = lens[slot]
            elif op == 1 and lens[slot] > 0:       # complete + index
                pool.alloc(slot, len(toks))
                pool.lens[slot] = len(toks)
                cache.insert(slot, toks)
            elif op == 2:                          # finish
                pool.free(slot)
                lens[slot] = 0
            elif op == 3 and lens[slot] > 0:       # decode growth
                pool.alloc(slot, min(int(pool.lens[slot]) + arg + 1,
                                     MAX_LEN))
        except KV.OutOfPagesError:
            pool.free(slot)                        # abort the request
            lens[slot] = 0
        pool.check_no_aliasing()
        assert pool.reclaimable_count() == sum(
            1 for n in cache._walk() if pool.refcount[n.page] == 0)
    for slot in range(3):
        pool.free(slot)
    cache.clear()
    pool.assert_all_free()


# ------------------------------------------- scheduler integration (stub)
class _FakeEngine:
    """Duck-typed engine (test_serving.py's pattern): scheduling logic
    only, so duplicate-prefix schedules run cheaply."""

    def __init__(self, cfg, max_len):
        self.cfg = cfg
        self.max_len = max_len

    def prefill_chunk(self, pages, pt, lens, tokens, logit_index, *,
                      page_size):
        return jnp.zeros((), jnp.int32), pages

    def decode_step(self, pages, pt, lens, mask, last, *, page_size):
        return last, pages


def _fake_cfg():
    from repro.models import model_zoo
    return model_zoo.reduced_config(model_zoo.get_config("stablelm-3b"))


def test_scheduler_duplicate_prompts_hit_and_audit_clean():
    cfg = _fake_cfg()
    rng = np.random.default_rng(3)
    shared = rng.integers(1, cfg.vocab_size, 18).astype(np.int32)
    reqs = [np.concatenate([shared,
                            rng.integers(1, cfg.vocab_size, 4)
                            .astype(np.int32)]) for _ in range(6)]
    sched = ContinuousBatchingScheduler(
        _FakeEngine(cfg, 48), batch_slots=2, prefill_chunk=8,
        page_size=8, check_invariants=True, prefix_cache=True)
    outs, stats = sched.run(reqs, [3] * 6)
    assert [len(o) for o in outs] == [3] * 6
    assert stats.prefix is not None and stats.prefix.hits >= 4
    assert stats.prefix.hit_tokens > 0
    # computed prefill = total prompt tokens minus what the cache covered
    assert stats.prefill_tokens == sum(len(r) for r in reqs) \
        - stats.prefix.hit_tokens
    hits = [ev for ev in sched.trace if ev[0] == "prefix_hit"]
    assert len(hits) == stats.prefix.hits
    sched.kv.check_no_aliasing()               # run() already audited


def test_scheduler_cache_survives_runs_and_pressure():
    """The index outlives run(): a second run over the same prompts hits
    warm, and a page-pressured run must evict instead of deadlocking."""
    cfg = _fake_cfg()
    rng = np.random.default_rng(4)
    reqs = [rng.integers(1, cfg.vocab_size, 20).astype(np.int32)
            for _ in range(3)]
    sched = ContinuousBatchingScheduler(
        _FakeEngine(cfg, 48), batch_slots=1, prefill_chunk=8,
        page_size=8, check_invariants=True, prefix_cache=True)
    sched.run(reqs, [2] * 3)
    h0 = sched.stats.prefix.hits
    sched.run(reqs, [2] * 3)                   # same prompts, warm index
    assert sched.stats.prefix.hits >= h0 + 3
    tight = ContinuousBatchingScheduler(
        _FakeEngine(cfg, 48), batch_slots=2, prefill_chunk=8,
        page_size=8, num_pages=4, check_invariants=True,
        prefix_cache=True)
    outs, stats = tight.run(
        [rng.integers(1, cfg.vocab_size, 14).astype(np.int32)
         for _ in range(5)], [2] * 5)
    assert [len(o) for o in outs] == [2] * 5
    assert stats.prefix.evicted_pages > 0
