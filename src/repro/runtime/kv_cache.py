"""Paged KV cache: physical page pool + host-side slot allocator.

The continuous-batching pool (runtime/batching.py) keeps a *static* slot
batch alive across requests; what changes per request is only which KV
storage a slot reads and writes.  A dense ``[slots, max_len]`` cache
would force the refill path to re-zero (or worse, re-allocate) a full
row per admitted request.  Instead the cache is paged, vLLM-style:

  * the device holds one physical pool per cached tensor,
    ``[layers, num_pages, page_size, *feat]``, which the serving steps
    read and write in place at the layer scan's index;
  * each slot owns an ordered list of page ids — its *page table* row —
    mapping logical token position ``p`` to physical location
    ``(table[p // page_size], p % page_size)``;
  * finishing a request returns its pages to the free list, and the next
    admitted request reuses them — no allocation, no recompile, no shape
    change anywhere on the device.

Numerics contract: ``paged_gather`` reconstructs the *logical-order*
dense view ``[slots, max_len, *feat]``, so attention over a paged cache
is bit-identical to attention over the dense cache it replaces (asserted
by tests/test_kv_cache.py on random alloc/free/refill traces, including
the wrap case where a long-lived slot outlives several neighbors).

Allocation is host-side (numpy + a free list): the scheduler calls
``alloc``/``free`` between device steps, and ships ``page_table``/
``lens`` as small int32 arrays into the jitted step — values change,
shapes never do.

Pages are REFCOUNTED so the cross-request prefix cache
(runtime/prefix_cache.py) can back many slots' tables with one physical
page: ``install`` maps already-written pages into a fresh slot
(incrementing their refcounts), ``fork`` is the copy-on-write escape —
a fresh page whose contents are copied from a shared one, so the new
slot can overwrite its tail without touching the original — and
``free`` only *decrements*; a page returns to the free list when its
last reference drops AND it is not registered as cached.  Cached pages
with refcount 0 are *reclaimable*: under pool pressure ``alloc``/
``fork`` call the registered evictor (the prefix cache's LRU sweep)
before declaring OutOfPages.  ``check_no_aliasing`` is refcount-aware
(a page in two live tables is legal exactly when its refcount says so)
and ``assert_all_free`` is the teardown leak audit: once every request
has been freed, every page must be free or cached-idle — a refcount
that never returned to zero is a leak the old free-list accounting
could not see.
"""
from __future__ import annotations

import collections

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime import faults as _faults

PAGE_FREE = -1
_LANES = 128          # a TPU vector register's lanes: the minor-dim tile


class OutOfPagesError(RuntimeError):
    """The free list cannot cover a requested allocation."""


class PageAliasError(RuntimeError):
    """A physical page's references disagree with its refcount (or a
    page is both live and free) — the invariant continuous batching
    must never break."""


class PageLeakError(PageAliasError):
    """A page kept a nonzero refcount (or a slot kept a mapping) after
    every request was freed — the silent leak ``assert_all_free``
    audits for at scheduler teardown."""


def pages_for(tokens: int, page_size: int) -> int:
    """Number of pages covering ``tokens`` token positions."""
    return -(-int(tokens) // page_size) if tokens > 0 else 0


def leaf_specs_for(cfg) -> dict:
    """Per-token cached tensors for ``cfg`` as ``{name: (feat, dtype)}``.

    Full-cache GQA and MLA are paged; window (ring) caches and SSM state
    are per-slot *constant-size* state with no paging win.

    MLA caches one row per token, ``pages_lat``: the normalised latent
    c_kv and the roped k_pe side by side (``kv_lora_rank + qk_rope_dim``,
    576 for DeepSeek-V2), zero-padded to whole 128-lane tiles (640), so
    one scatter writes a token and one gather reads a block.  Left 576
    wide, or split into a 512- and a 64-wide pool, the TPU lays the
    (narrow) pool out page-minor as it does narrow GQA rows (below), and
    each step copies it whole around the layer loop.

    The feature dims are the pool's minor dims, and a TPU keeps an array
    row-major only when its minor dim fills whole 128-lane tiles.  With
    such heads (128, 256, ...) a token's K (or V) is ``[num_kv_heads,
    head_dim]``, the shape attention reads.  Narrower heads (stablelm's
    80) are merged into one row of ``num_kv_heads * head_dim``: kept as
    ``[..., num_kv_heads, 80]`` the TPU would lay the pool out with the
    page dim minor, and every in-place scatter and gather would first
    copy the whole stacked pool to a row-major layout and back.  Either
    way the flat rows the serving steps index are a free reshape.
    """
    dt = jnp.dtype(cfg.cache_dtype)
    if cfg.attention_kind == "mla":
        return {"pages_lat": (
            (-(-(cfg.kv_lora_rank + cfg.qk_rope_dim) // _LANES) * _LANES,),
            dt)}
    if cfg.attention_kind != "gqa" or cfg.resolved_cache_kind != "full":
        raise NotImplementedError(
            f"paged KV cache supports full-cache GQA and MLA archs; got "
            f"attention_kind={cfg.attention_kind!r} / "
            f"cache={cfg.resolved_cache_kind!r}")
    hkv, hd = cfg.num_kv_heads, cfg.head_dim
    feat = (hkv, hd) if hd % _LANES == 0 else (hkv * hd,)
    return {"pages_k": (feat, dt), "pages_v": (feat, dt)}


# ------------------------------------------------------- device-side ops
# Both ops take the stacked pool ``[L, P, page_size, *feat]`` and the
# layer scan's index, and address the pool as flat rows
# ``[L·P·page_size, *feat]`` (a merge of major dims only), so the serving
# steps read and write the pool in place: no layer's pool is sliced out,
# copied or written back.
def _layer_rows(pages: jnp.ndarray, page_size: int, layer):
    """The stacked pool as flat rows, and the first row of ``layer``."""
    return (pages.reshape(-1, *pages.shape[3:]),
            layer * (pages.shape[1] * page_size))


def paged_gather(pages: jnp.ndarray, page_table: jnp.ndarray,
                 page_size: int, layer) -> jnp.ndarray:
    """Dense logical view of layer ``layer`` of a paged pool.

    pages: the stacked pool [L, P, page_size, *feat]; layer: int32
    scalar (traced in the layer scan); page_table: [B, n_view] int32
    physical ids (-1 = unmapped).  Returns [B, n_view * page_size, *feat],
    gathered straight from ``pages``; the contents of unmapped pages are
    arbitrary (physical page 0) — callers mask them by position, exactly
    as the dense cache masks its zero-initialized tail.  Its ops carry
    the device scope ``kv_view``.
    """
    b, n_view = page_table.shape
    with jax.named_scope("kv_view"):
        flat, row0 = _layer_rows(pages, page_size, layer)
        base = row0 + jnp.where(page_table >= 0, page_table, 0) * page_size
        idx = base[:, :, None] + jnp.arange(page_size)[None, None, :]
        return flat[idx.reshape(b, n_view * page_size)]


def paged_update(pages: jnp.ndarray, new: jnp.ndarray,
                 page_table: jnp.ndarray, lens: jnp.ndarray,
                 page_size: int, layer, write_mask=None) -> jnp.ndarray:
    """Scatter ``new[b, i]`` to logical position ``lens[b] + i`` of slot b,
    in layer ``layer`` of the pool.

    pages: the stacked pool [L, P, page_size, *feat]; layer: int32
    scalar (traced in the layer scan); new: [B, s, ...], a token's
    ``prod(feat)`` values, cast to the pool's dtype; lens: [B] int32;
    write_mask: optional [B] bool — rows with False (slots that are
    admitted but not decoding this step, or idle) write nothing.  Writes
    through unmapped table entries (-1) or past the table end are
    dropped, so chunk padding rows and masked slots can never touch a
    freed or foreign page.  Returns the pool in its shape; under a
    donated buffer the scatter updates it in place.  Its ops carry the
    device scope ``kv_write``.
    """
    b, s = new.shape[0], new.shape[1]
    n_view = page_table.shape[1]
    with jax.named_scope("kv_write"):
        flat, row0 = _layer_rows(pages, page_size, layer)
        pos = lens[:, None] + jnp.arange(s)[None, :]          # [B, s]
        page_idx = pos // page_size
        phys = jnp.take_along_axis(page_table,
                                   jnp.clip(page_idx, 0, n_view - 1), axis=1)
        valid = (phys >= 0) & (page_idx < n_view)
        if write_mask is not None:
            valid &= write_mask[:, None]
        oob = flat.shape[0]                                   # drop sentinel
        flat_idx = jnp.where(valid, row0 + phys * page_size + pos % page_size,
                             oob)
        flat = flat.at[flat_idx.reshape(b * s)].set(
            new.reshape(b * s, *flat.shape[1:]).astype(flat.dtype),
            mode="drop")
        return flat.reshape(pages.shape)


# ------------------------------------------------------ host-side pool
class PagedKVCache:
    """Physical page pool + per-slot page tables and length counters.

    The device arrays in ``self.pages`` are *threaded* through the jitted
    serving steps (donated and replaced each call); ``page_table`` /
    ``lens`` live here as numpy and are shipped per call via
    ``table_device()`` / ``lens_device()``.
    """

    def __init__(self, *, num_layers: int, num_slots: int, max_len: int,
                 page_size: int, leaf_specs: dict, num_pages: int | None = None):
        if max_len % page_size:
            raise ValueError(f"max_len={max_len} must be a multiple of "
                             f"page_size={page_size} so the gathered view "
                             f"matches the dense cache length exactly")
        self.num_slots = num_slots
        self.max_len = max_len
        self.page_size = page_size
        self.pages_per_slot = max_len // page_size
        self.num_pages = (num_pages if num_pages is not None
                          else num_slots * self.pages_per_slot)
        self.pages = {
            name: jnp.zeros((num_layers, self.num_pages, page_size, *feat),
                            dtype)
            for name, (feat, dtype) in leaf_specs.items()}
        self.page_table = np.full((num_slots, self.pages_per_slot),
                                  PAGE_FREE, np.int32)
        self.lens = np.zeros((num_slots,), np.int32)
        self._n_pages = np.zeros((num_slots,), np.int32)
        self._free: collections.deque[int] = collections.deque(
            range(self.num_pages))
        # prefix-cache support: per-page reference counts (slot-table
        # references only — the cache index itself holds none, which is
        # what makes refcount-0 cached pages the reclaimable set), the
        # cached-page registry, and the pressure evictor hook
        self.refcount = np.zeros((self.num_pages,), np.int32)
        self._cached: set[int] = set()
        self._evictor = None

    # ------------------------------------------------------- allocation
    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def cached_count(self) -> int:
        """Pages registered by a prefix cache (live or idle)."""
        return len(self._cached)

    def is_cached(self, p: int) -> bool:
        """True if page ``p`` is registered by a prefix cache."""
        return int(p) in self._cached

    def reclaimable_count(self, exclude=()) -> int:
        """Cached pages with refcount 0 — what the evictor could return
        to the free list under pressure.  ``exclude`` discounts pages a
        caller is about to pin (an admission's own prefix hit must not
        count toward the budget that admits it)."""
        skip = set(int(p) for p in exclude)
        return sum(1 for p in self._cached
                   if self.refcount[p] == 0 and p not in skip)

    def set_evictor(self, fn) -> None:
        """Register ``fn(n_pages) -> freed`` called under pool pressure
        before OutOfPagesError; the prefix cache's LRU sweep."""
        self._evictor = fn

    def held(self, slot: int) -> int:
        """Pages currently mapped by ``slot``."""
        return int(self._n_pages[slot])

    def _take_free(self, why: str) -> int:
        """Pop a free page (evicting reclaimable cached pages first under
        pressure); the caller owns its single reference."""
        # chaos injection point: an injected OOM fires before any state
        # changes, so the scheduler's quarantine sees a consistent pool
        _faults.maybe_fire("alloc_oom", why=why)
        if not self._free and self._evictor is not None:
            self._evictor(1)
        if not self._free:
            raise OutOfPagesError(
                f"{why} but the free list is empty "
                f"({self.num_pages} pages total, "
                f"{self.cached_count} cached)")
        p = self._free.popleft()
        self.refcount[p] = 1
        return p

    def _release(self, p: int) -> bool:
        """Drop one reference to ``p``; True if it returned to the free
        list (last reference gone and not retained by the cache)."""
        if self.refcount[p] <= 0:
            raise PageAliasError(f"double free of page {p}")
        self.refcount[p] -= 1
        if self.refcount[p] == 0 and p not in self._cached:
            self._free.append(p)
            return True
        return False

    def alloc(self, slot: int, token_len: int) -> None:
        """Grow ``slot``'s mapping to cover ``token_len`` logical tokens."""
        target = pages_for(token_len, self.page_size)
        if target > self.pages_per_slot:
            raise ValueError(f"slot {slot}: {token_len} tokens exceed "
                             f"max_len={self.max_len}")
        while self._n_pages[slot] < target:
            p = self._take_free(
                f"slot {slot} needs page {int(self._n_pages[slot])}")
            self.page_table[slot, self._n_pages[slot]] = p
            self._n_pages[slot] += 1

    def install(self, slot: int, pages) -> None:
        """Map already-written ``pages`` (a cached prefix run, in logical
        order) as the head of ``slot``'s table, taking one reference
        each.  The slot must hold no mapping yet — prefix installation
        happens at admission, before any alloc."""
        if self._n_pages[slot]:
            raise PageAliasError(
                f"install into slot {slot} which already maps "
                f"{self.held(slot)} pages")
        if len(pages) > self.pages_per_slot:
            raise ValueError(f"slot {slot}: {len(pages)} shared pages "
                             f"exceed max_len={self.max_len}")
        for j, p in enumerate(pages):
            p = int(p)
            if self.refcount[p] == 0 and p not in self._cached:
                raise PageAliasError(
                    f"install of page {p} which is neither live nor "
                    f"cached (would alias the free list)")
            self.refcount[p] += 1
            self.page_table[slot, j] = p
        self._n_pages[slot] = len(pages)

    def fork(self, slot: int, src_page: int) -> int:
        """Copy-on-write: map a FRESH page as ``slot``'s next table entry
        with the contents of ``src_page`` copied in (device-side, every
        leaf pool), so the slot can overwrite the copied tail without
        touching the shared original.  Returns the new physical id."""
        j = int(self._n_pages[slot])
        if j >= self.pages_per_slot:
            raise ValueError(f"slot {slot}: fork past max_len")
        # pin the source across the take: under pressure the evictor
        # could otherwise reclaim src itself and hand it back as dst
        self.refcount[src_page] += 1
        try:
            dst = self._take_free(f"slot {slot} forking page {src_page}")
        finally:
            self._release(int(src_page))
        for name, arr in self.pages.items():
            self.pages[name] = arr.at[:, dst].set(arr[:, src_page])
        self.page_table[slot, j] = dst
        self._n_pages[slot] = j + 1
        return dst

    def free(self, slot: int) -> list[int]:
        """Release every reference of ``slot``; returns the ids that
        actually came back to the free list (shared pages survive with
        the remaining holders; cached pages are retained reclaimable)."""
        n = int(self._n_pages[slot])
        freed = [p for p in map(int, self.page_table[slot, :n])
                 if self._release(p)]
        self.page_table[slot, :] = PAGE_FREE
        self._n_pages[slot] = 0
        self.lens[slot] = 0
        return freed

    # ----------------------------------------- prefix-cache page registry
    def mark_cached(self, pages) -> None:
        """Register ``pages`` as retained by the prefix index: their last
        ``free`` keeps them out of the free list (reclaimable by the
        evictor instead of recycled)."""
        for p in pages:
            p = int(p)
            if self.refcount[p] == 0 and p not in self._cached:
                raise PageAliasError(
                    f"mark_cached on free page {p}")
            self._cached.add(p)

    def uncache(self, pages) -> list[int]:
        """Drop ``pages`` from the cached registry (eviction / index
        clear); idle ones return to the free list immediately."""
        freed = []
        for p in pages:
            p = int(p)
            if p in self._cached:
                self._cached.discard(p)
                if self.refcount[p] == 0:
                    self._free.append(p)
                    freed.append(p)
        return freed

    def reset(self) -> None:
        """Full pool reset: every slot freed AND the cached registry
        dropped (a prefix index over this pool must be discarded with
        it)."""
        for s in range(self.num_slots):
            if self._n_pages[s]:
                self.free(s)
        self.lens[:] = 0
        self.uncache(list(self._cached))

    # -------------------------------------------------- device shipping
    # Both ship a host COPY: on the CPU backend ``jnp.asarray`` may alias
    # a numpy buffer without copying, and the scheduler mutates
    # ``page_table``/``lens`` in place right after an asynchronous
    # dispatch, so an aliased step could read the next tick's values.
    def table_device(self, slots=None) -> jnp.ndarray:
        t = self.page_table if slots is None else self.page_table[slots]
        return jnp.asarray(np.array(t, copy=True))

    def lens_device(self, slots=None) -> jnp.ndarray:
        l = self.lens if slots is None else self.lens[slots]
        return jnp.asarray(np.array(l, copy=True))

    # ---------------------------------------------------- invariants
    def check_no_aliasing(self) -> None:
        """Raise PageAliasError unless table references, refcounts, the
        cached registry and the free list are mutually consistent:
        every page's refcount equals its table references (sharing is
        legal exactly when the refcount says so), the free list holds
        no duplicates and no referenced or cached page, and
        free + live + cached-idle partitions the physical pool."""
        refs = np.zeros((self.num_pages,), np.int64)
        for row in self.page_table:
            for p in row:
                if p >= 0:
                    refs[p] += 1
        bad = np.flatnonzero(refs != self.refcount)
        if bad.size:
            detail = ", ".join(
                f"page {p}: {refs[p]} table refs vs refcount "
                f"{int(self.refcount[p])}" for p in bad[:4])
            raise PageAliasError(f"refcount mismatch ({detail})")
        free = list(self._free)
        if len(free) != len(set(free)):
            dup = sorted(p for p in set(free) if free.count(p) > 1)
            raise PageAliasError(f"pages {dup} twice on the free list")
        overlap = set(free) & set(np.flatnonzero(refs > 0).tolist())
        if overlap:
            raise PageAliasError(
                f"pages {sorted(overlap)} both live and free")
        overlap = set(free) & self._cached
        if overlap:
            raise PageAliasError(
                f"pages {sorted(overlap)} both cached and free")
        live = int(np.count_nonzero(refs > 0))
        idle_cached = sum(1 for p in self._cached if refs[p] == 0)
        if live + idle_cached + len(free) != self.num_pages:
            raise PageAliasError(
                f"page leak: {live} live + {idle_cached} cached-idle "
                f"+ {len(free)} free != {self.num_pages} total")

    def assert_all_free(self) -> None:
        """Teardown leak audit: with no request live, every page must be
        free or cached-idle.  A nonzero refcount here is the silent
        leak the plain free-list accounting missed when a request was
        freed while its pages were shared (raises PageLeakError)."""
        self.check_no_aliasing()
        held = np.flatnonzero(self._n_pages > 0)
        if held.size:
            raise PageLeakError(
                f"slots {held.tolist()} still hold mappings at teardown")
        live = np.flatnonzero(self.refcount > 0)
        if live.size:
            raise PageLeakError(
                f"pages {live.tolist()} kept nonzero refcounts at "
                f"teardown — a free() path dropped a reference short")
        if len(self._free) + len(self._cached) != self.num_pages:
            raise PageLeakError(
                f"{len(self._free)} free + {len(self._cached)} cached "
                f"!= {self.num_pages} pages at teardown")
