"""Paged KV cache in isolation: alloc/free/refill round-trips must equal
a dense [B, max_len] cache on random decode traces (including the wrap
case where a long-running slot outlives several refilled neighbors), the
allocator must never alias or leak a page, and the serving steps' in-place
update and gather of the stacked pool must equal slicing out a layer,
updating and gathering it, and writing it back."""
import dataclasses

import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:      # dev extra (requirements-dev.txt)
    from _hypothesis_stub import given, settings, st

import jax
import jax.numpy as jnp

import _kv_guard
from repro.models import model_zoo
from repro.runtime import kv_cache as KV
from repro.runtime.serve_loop import Engine

PAGE = 4
MAX_LEN = 16
FEAT = (2, 3)


def _pool(num_slots=3, num_pages=None):
    return KV.PagedKVCache(
        num_layers=1, num_slots=num_slots, max_len=MAX_LEN,
        page_size=PAGE, leaf_specs={"pages_k": (FEAT, jnp.float32)},
        num_pages=num_pages)


def _write(pool, slot, n_new, rng, dense):
    """Write n_new tokens to `slot` through the paged path AND the dense
    reference; returns nothing (mutates both)."""
    start = int(pool.lens[slot])
    pool.alloc(slot, start + n_new)
    vals = rng.standard_normal((1, n_new, *FEAT)).astype(np.float32)
    pool.pages["pages_k"] = KV.paged_update(
        pool.pages["pages_k"], jnp.asarray(vals),
        pool.table_device([slot]), pool.lens_device([slot]), PAGE, 0)
    dense[slot, start:start + n_new] = vals[0]
    pool.lens[slot] = start + n_new


def _check_equal(pool, dense):
    view = np.asarray(KV.paged_gather(pool.pages["pages_k"],
                                      pool.table_device(), PAGE, 0))
    for b in range(pool.num_slots):
        n = int(pool.lens[b])
        np.testing.assert_array_equal(view[b, :n], dense[b, :n])


# ------------------------------------------------------------ round trips
def test_roundtrip_single_slot():
    rng = np.random.default_rng(0)
    pool = _pool()
    dense = np.zeros((3, MAX_LEN, *FEAT), np.float32)
    _write(pool, 0, 5, rng, dense)      # ragged prefill chunk
    _write(pool, 0, 1, rng, dense)      # decode steps
    _write(pool, 0, 1, rng, dense)
    _check_equal(pool, dense)
    pool.check_no_aliasing()


def test_refill_reuses_freed_pages_wrap_case():
    """Slot 0 outlives several refilled neighbors; the neighbors' reused
    pages must never perturb slot 0's data."""
    rng = np.random.default_rng(1)
    pool = _pool(num_slots=3, num_pages=8)   # tight: forces real reuse
    dense = np.zeros((3, MAX_LEN, *FEAT), np.float32)
    _write(pool, 0, 9, rng, dense)           # long-running resident
    seen_pages = set()
    for cycle in range(4):                   # neighbors churn
        for slot in (1, 2):
            _write(pool, slot, 3 + cycle, rng, dense)
            _check_equal(pool, dense)
            seen_pages.update(
                int(p) for p in pool.page_table[slot] if p >= 0)
            freed = pool.free(slot)
            dense[slot] = 0.0
            assert freed, "neighbor held pages"
            pool.check_no_aliasing()
    _write(pool, 0, 2, rng, dense)           # resident keeps decoding
    _check_equal(pool, dense)
    # churn actually recycled physical pages (the wrap happened)
    assert len(seen_pages) <= pool.num_pages
    assert any(p in seen_pages
               for p in pool.page_table[0] if p >= 0) or len(seen_pages) < 8


def test_random_trace_matches_dense():
    rng = np.random.default_rng(2)
    pool = _pool(num_slots=4)
    dense = np.zeros((4, MAX_LEN, *FEAT), np.float32)
    for _ in range(200):
        slot = int(rng.integers(4))
        room = MAX_LEN - int(pool.lens[slot])
        if rng.random() < 0.2 and pool.lens[slot] > 0:
            pool.free(slot)
            dense[slot] = 0.0
        elif room > 0:
            _write(pool, slot, int(rng.integers(1, min(room, 6) + 1)),
                   rng, dense)
        pool.check_no_aliasing()
    _check_equal(pool, dense)


# ----------------------------------------------------- write-drop guards
def test_write_mask_drops_rows():
    rng = np.random.default_rng(3)
    pool = _pool(num_slots=2)
    dense = np.zeros((2, MAX_LEN, *FEAT), np.float32)
    _write(pool, 0, 4, rng, dense)
    _write(pool, 1, 4, rng, dense)
    pool.alloc(0, 5)                     # room for the unmasked write
    vals = rng.standard_normal((2, 1, *FEAT)).astype(np.float32)
    pool.pages["pages_k"] = KV.paged_update(
        pool.pages["pages_k"], jnp.asarray(vals), pool.table_device(),
        pool.lens_device(), PAGE, 0, write_mask=jnp.asarray([True, False]))
    dense[0, 4] = vals[0, 0]             # row 1 masked: writes nothing
    pool.lens[0] += 1
    _check_equal(pool, dense)


def test_unmapped_writes_dropped():
    """Writes through -1 table entries (idle slot / chunk padding past
    the allocation) must not corrupt page 0."""
    rng = np.random.default_rng(4)
    pool = _pool(num_slots=2)
    dense = np.zeros((2, MAX_LEN, *FEAT), np.float32)
    _write(pool, 0, 4, rng, dense)       # slot 0 owns page 0
    vals = rng.standard_normal((1, 3, *FEAT)).astype(np.float32)
    # slot 1 has NO pages mapped; its write must vanish, not land in
    # someone else's page
    pool.pages["pages_k"] = KV.paged_update(
        pool.pages["pages_k"], jnp.asarray(vals),
        pool.table_device([1]), pool.lens_device([1]), PAGE, 0)
    _check_equal(pool, dense)


# --------------------------------------------------------- allocator law
def test_alloc_oom_raises():
    pool = _pool(num_slots=2, num_pages=2)
    pool.alloc(0, 8)                      # 2 pages: pool exhausted
    with pytest.raises(KV.OutOfPagesError):
        pool.alloc(1, 1)


def test_alloc_beyond_max_len_raises():
    pool = _pool()
    with pytest.raises(ValueError):
        pool.alloc(0, MAX_LEN + 1)


def test_free_returns_pages_and_resets():
    pool = _pool()
    pool.alloc(0, 10)
    held = pool.held(0)
    assert held == KV.pages_for(10, PAGE) == 3
    freed = pool.free(0)
    assert len(freed) == held
    assert pool.held(0) == 0 and int(pool.lens[0]) == 0
    assert pool.free_count == pool.num_pages
    pool.check_no_aliasing()


def _aligned(a):
    """``a`` copied into a 64-byte-aligned buffer: JAX's CPU client
    aliases such a host array instead of copying it."""
    buf = np.empty(a.nbytes + 64, np.uint8)
    off = -buf.ctypes.data % 64
    out = buf[off:off + a.nbytes].view(a.dtype).reshape(a.shape)
    out[...] = a
    return out


def test_shipped_table_and_lens_do_not_alias_host_state():
    """The scheduler changes ``page_table``/``lens`` in place right
    after an asynchronous dispatch; what a step was handed must not
    change with them."""
    pool = _pool()
    pool.page_table, pool.lens = _aligned(pool.page_table), _aligned(pool.lens)
    pool.alloc(0, 4)
    pool.lens[0] = 4
    want_table, want_lens = pool.page_table.copy(), pool.lens.copy()
    table, lens = pool.table_device(), pool.lens_device()
    pool.lens[0] += 1
    pool.page_table[0, 0] = KV.PAGE_FREE
    np.testing.assert_array_equal(np.asarray(table), want_table)
    np.testing.assert_array_equal(np.asarray(lens), want_lens)


def test_aliasing_detected():
    pool = _pool()
    pool.alloc(0, 4)
    pool.page_table[1, 0] = pool.page_table[0, 0]     # corrupt: alias
    with pytest.raises(KV.PageAliasError):
        pool.check_no_aliasing()


def test_leak_detected():
    pool = _pool()
    pool.alloc(0, 4)
    pool.page_table[0, 0] = KV.PAGE_FREE              # drop w/o freeing
    with pytest.raises(KV.PageAliasError):
        pool.check_no_aliasing()


def test_leaf_specs_rejects_unsupported_arch():
    cfg = model_zoo.reduced_config(model_zoo.get_config("mamba2-370m"))
    with pytest.raises(NotImplementedError):
        KV.leaf_specs_for(cfg)


def test_max_len_page_divisibility():
    with pytest.raises(ValueError):
        KV.PagedKVCache(num_layers=1, num_slots=1, max_len=10,
                        page_size=4,
                        leaf_specs={"pages_k": (FEAT, jnp.float32)})


# ------------------------------------------------- refcount / COW laws
def test_install_shares_and_free_respects_holders():
    pool = _pool()
    pool.alloc(0, 8)
    pages = [int(p) for p in pool.page_table[0, :2]]
    pool.mark_cached(pages)
    pool.install(1, pages)
    assert all(pool.refcount[p] == 2 for p in pages)
    pool.check_no_aliasing()
    assert pool.free(0) == []            # slot 1 still holds them
    assert pool.free(1) == []            # cached: retained reclaimable
    assert pool.reclaimable_count() == 2
    assert pool.free_count == pool.num_pages - 2
    pool.assert_all_free()               # cached-idle is not a leak


def test_install_guards():
    pool = _pool()
    pool.alloc(0, 4)
    with pytest.raises(KV.PageAliasError):
        pool.install(0, [int(pool.page_table[0, 0])])   # slot not empty
    free_page = pool._free[0]
    with pytest.raises(KV.PageAliasError):
        pool.install(1, [free_page])     # neither live nor cached


def test_fork_copies_and_isolates_writes():
    rng = np.random.default_rng(5)
    pool = _pool(num_slots=2)
    dense = np.zeros((2, MAX_LEN, *FEAT), np.float32)
    _write(pool, 0, 4, rng, dense)       # slot 0 fills page 0
    src = int(pool.page_table[0, 0])
    dst = pool.fork(1, src)
    assert dst != src and pool.refcount[src] == 1 == pool.refcount[dst]
    pool.lens[1] = 2                     # reuse the copied head...
    dense[1, :2] = dense[0, :2]
    _write(pool, 1, 2, rng, dense)       # ...overwrite the tail
    _check_equal(pool, dense)            # slot 0's page untouched
    pool.check_no_aliasing()


def test_fork_under_pressure_evicts_other_cached_pages_not_src():
    pool = _pool(num_slots=2, num_pages=2)
    pool.alloc(0, 8)                     # pool exhausted
    a, b = (int(p) for p in pool.page_table[0, :2])
    pool.mark_cached([a, b])
    pool.free(0)                         # both cached-idle
    evicted = []

    def evictor(n):                      # reclaim any refcount-0 page
        for p in (a, b):
            if len(evicted) < n and pool.refcount[p] == 0:
                evicted.extend(pool.uncache([p]))
        return len(evicted)

    pool.set_evictor(evictor)
    dst = pool.fork(1, b)                # src b pinned across the take
    assert evicted == [a] and dst == a   # the OTHER page was reclaimed
    assert pool.refcount[b] == 0 and b in pool._cached
    pool.check_no_aliasing()


def test_double_free_detected():
    pool = _pool()
    pool.alloc(0, 4)
    p = int(pool.page_table[0, 0])
    pool.free(0)
    with pytest.raises(KV.PageAliasError, match="double free"):
        pool._release(p)


def test_uncache_returns_idle_pages_only():
    pool = _pool()
    pool.alloc(0, 4)
    p = int(pool.page_table[0, 0])
    pool.mark_cached([p])
    assert pool.uncache([p]) == []       # still live: retained
    assert pool.cached_count == 0
    pool.free(0)
    assert pool.free_count == pool.num_pages


def test_assert_all_free_flags_leaks():
    pool = _pool()
    pool.alloc(0, 4)
    with pytest.raises(KV.PageLeakError):
        pool.assert_all_free()
    pool.free(0)
    pool.assert_all_free()


# ------------------------------------------------------------- property
@settings(max_examples=60, deadline=None)
@given(ops=st.lists(st.tuples(st.integers(0, 3), st.integers(0, 9)),
                    min_size=1, max_size=60),
       num_pages=st.integers(4, 12))
def test_allocator_invariants_property(ops, num_pages):
    """Random alloc/free sequences never alias, never leak, and held
    page counts always match the lengths they cover."""
    pool = _pool(num_slots=4, num_pages=num_pages)
    lens = [0, 0, 0, 0]
    for slot, amount in ops:
        if amount == 0:
            pool.free(slot)
            lens[slot] = 0
        else:
            target = min(lens[slot] + amount, MAX_LEN)
            try:
                pool.alloc(slot, target)
            except KV.OutOfPagesError:
                continue
            lens[slot] = target
            pool.lens[slot] = target
        pool.check_no_aliasing()
        for b in range(4):
            assert pool.held(b) >= KV.pages_for(lens[b], PAGE)


# ------------------------------------------- the stacked pool, in place
LAYERS = 3


def _sliced_reference(pool, layer, new, table, lens, mask):
    """Slice layer ``layer`` out of the stacked pool, write ``new`` into
    it token by token, gather the slots' view, write the layer back: the
    plain numpy form of what the serving steps did before they indexed
    the pool in place."""
    pool = np.array(pool)
    lay = pool[layer]
    vals = np.asarray(jnp.asarray(new).astype(pool.dtype))
    n_view = table.shape[1]
    for b in range(new.shape[0]):
        if not mask[b]:
            continue
        for i in range(new.shape[1]):
            pos = int(lens[b]) + i
            page = pos // PAGE
            if page < n_view and table[b, page] >= 0:
                lay[table[b, page], pos % PAGE] = vals[b, i]
    rows = np.where(table >= 0, table, 0)
    view = lay[rows].reshape(table.shape[0], n_view * PAGE, *lay.shape[2:])
    pool[layer] = lay
    return pool, view


@jax.jit
def _in_place_step(pages, new, table, lens, mask, layer):
    pages = KV.paged_update(pages, new, table, lens, PAGE, layer,
                            write_mask=mask)
    return pages, KV.paged_gather(pages, table, PAGE, layer)


def _in_place(pool, layer, new, table, lens, mask):
    """The serving steps' form: scatter and gather at ``layer`` of the
    stacked pool (a traced index, as the layer scan hands it down)."""
    return _in_place_step(pool, jnp.asarray(new), jnp.asarray(table),
                          jnp.asarray(lens), jnp.asarray(mask),
                          jnp.int32(layer))


@pytest.mark.parametrize("feat", [FEAT, (6,)], ids=["heads", "merged"])
@pytest.mark.parametrize("seed", range(4))
def test_in_place_layer_update_matches_slice_and_write_back(seed, feat):
    """Random alloc/free/refill traces, with prefix pages shared by
    ``install`` and copied by ``fork``, unmapped (-1) entries, chunk rows
    past the table end and masked rows: the view and every layer of the
    pool match the slice-update-gather-write-back reference bit for bit,
    at layer 0, at the last layer and between."""
    rng = np.random.default_rng(100 + seed)
    slots = 3
    pool = KV.PagedKVCache(
        num_layers=LAYERS, num_slots=slots, max_len=MAX_LEN, page_size=PAGE,
        leaf_specs={"pages_k": (feat, jnp.bfloat16)}, num_pages=10)
    pool.pages["pages_k"] = jnp.asarray(rng.standard_normal(
        (LAYERS, pool.num_pages, PAGE, *feat)), jnp.bfloat16)
    seen = set()
    for step in range(40):
        op = rng.random()
        slot = int(rng.integers(slots))
        if op < 0.15 and pool.held(slot):
            pool.free(slot)
            continue
        if op < 0.3:
            # share another slot's whole pages into an emptied slot, and
            # fork its partly written page as this slot's next page
            src = int(rng.integers(slots))
            full = int(pool.lens[src]) // PAGE
            if src != slot and full:
                pool.free(slot)
                shared = [int(p) for p in pool.page_table[src, :full]]
                pool.mark_cached(shared)
                pool.install(slot, shared)
                pool.lens[slot] = full * PAGE
                if pool.lens[src] % PAGE and pool.free_count:
                    pool.fork(slot, int(pool.page_table[src, full]))
                    pool.lens[slot] += int(rng.integers(pool.lens[src] % PAGE))
                continue
        rows = [slot] if rng.random() < 0.5 else list(range(slots))
        s = int(rng.integers(1, 6))
        for r in rows:
            # cover only part of the chunk now and then: its tail rows
            # then meet unmapped entries
            want = min(int(pool.lens[r]) + int(rng.integers(0, s + 1)),
                       MAX_LEN)
            try:
                pool.alloc(r, want)
            except KV.OutOfPagesError:
                pass
        table, lens = pool.page_table[rows].copy(), pool.lens[rows].copy()
        mask = rng.random(len(rows)) < 0.75
        layer = (0, LAYERS - 1)[step % 2] if step < 4 else int(
            rng.integers(LAYERS))
        seen.add(layer)
        new = rng.standard_normal((len(rows), s, *feat)).astype(np.float32)
        want_pool, want_view = _sliced_reference(
            pool.pages["pages_k"], layer, new, table, lens, mask)
        got_pool, got_view = _in_place(pool.pages["pages_k"], layer, new,
                                       table, lens, mask)
        np.testing.assert_array_equal(np.asarray(got_view), want_view)
        np.testing.assert_array_equal(np.asarray(got_pool), want_pool)
        pool.pages["pages_k"] = got_pool
        for r, m in zip(rows, mask):
            if m:
                pool.lens[r] = min(int(pool.lens[r]) + s,
                                   pool.held(r) * PAGE)
        pool.check_no_aliasing()
    assert {0, LAYERS - 1} <= seen


def test_pool_rows_merge_heads_narrower_than_a_lane_tile():
    cfg = model_zoo.reduced_config(model_zoo.get_config("stablelm-3b"))
    (feat, _), = {KV.leaf_specs_for(cfg)["pages_k"]}
    assert feat == (cfg.num_kv_heads * cfg.head_dim,)
    wide = dataclasses.replace(cfg, head_dim=128)
    assert KV.leaf_specs_for(wide)["pages_v"][0] == (cfg.num_kv_heads, 128)


# ---------------------------------------------------------- HLO guard
_GUARD_HLO = """
  %pool = bf16[2,12,8,32]{3,2,1,0} parameter(0)
  %flat = bf16[192,32]{1,0} bitcast(%pool)
  %scatter.1 = bf16[192,32]{1,0} scatter(%flat, %i, %u), metadata={op_name="jit(_paged_prefill)/while/body/kv_write/scatter"}
  %gather.1 = bf16[96,32]{1,0} gather(%scatter.1, %j), metadata={op_name="jit(_paged_decode)/while/body/kv_view/gather"}
  %copy.1 = bf16[96,32]{0,1} copy(%gather.1), metadata={op_name="jit(_paged_decode)/while/body/kv_view/gather"}
  %pad.1 = bf16[3,64,32]{2,1,0} pad(%copy.1, %z), metadata={op_name="jit(_paged_decode)/while/body/kv_view/jit(_pad)/pad"}
  %copy.2 = bf16[96,32]{0,1} copy(%flat), metadata={op_name="jit(_paged_decode)/while/body/kv_view/gather"}
  %copy.3 = bf16[2,12,8,32]{0,3,2,1} copy(%pool)
  %dynamic-slice.1 = bf16[1,12,8,32]{3,2,1,0} dynamic-slice(%pool, %l), metadata={op_name="jit(_paged_prefill)/while/body/kv_view/dynamic_slice"}
  %fusion.1 = bf16[2,12,8,32]{3,2,1,0} fusion(%pool, %d), kind=kLoop, metadata={op_name="jit(_paged_prefill)/while/body/kv_write/dynamic_update_slice"}
  %fusion.2 = bf16[1,12,8,32]{3,2,1,0} fusion(%w), kind=kLoop, metadata={op_name="jit(_paged_prefill)/while/body/ffn/dot_general"}
  %copy.4 = bf16[8,32]{1,0} copy(%flat)
"""


def test_the_guard_flags_pool_copies_and_passes_the_scatter_and_view():
    found = _kv_guard.pool_copies(_GUARD_HLO, 12 * 8 * 32)
    bad = {b.split()[0] for b in found}
    # a copy of the pool posing as the view's relayout, a layout copy of
    # the stacked pool, a layer's slice and its write-back
    assert bad == {"copy.2", "copy.3", "dynamic-slice.1", "fusion.1"}


@pytest.mark.parametrize("head_dim", [16, 128])
def test_paged_steps_compile_without_pool_copies(head_dim):
    """The compiled prefill-chunk, decode and megastep steps of a small
    GQA model move no array of a layer pool's size but the in-place
    scatter (and the views, which are smaller here: the pool has spare
    pages)."""
    cfg = dataclasses.replace(
        model_zoo.reduced_config(model_zoo.get_config("stablelm-3b")),
        head_dim=head_dim)
    eng = Engine(cfg, model_zoo.build(cfg), max_len=32, packed=False)
    num_pages = 12                  # 2 slots x 4 pages, and spares
    texts = _kv_guard.step_texts(eng, slots=2, chunk=8, page_size=8,
                                 num_pages=num_pages)
    layer = _kv_guard.layer_pool_elements(eng, num_pages=num_pages,
                                          page_size=8)
    for name, text in texts.items():
        assert "scatter" in text and "gather" in text, name
        assert _kv_guard.pool_copies(text, layer) == [], name
