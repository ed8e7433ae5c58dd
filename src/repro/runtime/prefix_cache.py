"""Cross-request prefix cache: a radix index over the paged KV pool.

Production traffic shares system prompts and few-shot preambles; the
biggest deployment lever above the inner loop is not recomputing that
work at all.  The paged KV cache already gives page-granular identity —
a physical page holds the KV of exactly ``page_size`` consecutive
tokens of one token run — so cached prefixes compose out of pages:

  * **The index is a trie over page-aligned token runs.**  Each node
    owns one physical page and the ``page_size``-token run it encodes;
    the path from the root spells a cached prefix.  Children are keyed
    by their full token run, so a full-page match is one dict lookup,
    and sibling runs that share a head diverge exactly like a radix
    tree's edges split.
  * **Hits install pages, not values.**  ``admit`` maps the matched
    run's pages into the new slot's page table (``PagedKVCache.install``
    increments each page's refcount) and the scheduler starts chunked
    prefill at the first token the cache does not cover.  At least one
    token is always recomputed — the final prompt position's logits
    seed generation and are never cached.
  * **Copy-on-write at the divergence page.**  When the prompt runs
    into a cached page but diverges (or ends) inside it, the page
    cannot be shared — the new request must overwrite its tail — so it
    is COW-forked: ``PagedKVCache.fork`` copies the page into a fresh
    one mapped privately to the slot, the matching head positions ride
    along for free, and prefill resumes mid-page at the divergent
    token.
  * **Insertion at prefill completion.**  Once a prompt is fully
    prefilled its full prompt pages are immutable (decode writes land
    strictly past the prompt; a partial final page is never indexed),
    so the trie walks the prompt and registers the slot's pages for
    every run not already cached (``mark_cached`` keeps them off the
    free list when the request finishes).
  * **Eviction is LRU over refcount-0 leaves.**  The index holds no
    refcounts itself: a cached page referenced by no live slot is
    *reclaimable*.  Under pool pressure the allocator calls
    :meth:`PrefixCache._evict`, which removes least-recently-touched
    refcount-0 leaf nodes (cascading upward as parents become leaves)
    until the demand is met.  Because a hit always installs the full
    root path, a live page's ancestors are live too — so every
    refcount-0 page is reachable by the leaf cascade and
    ``reclaimable_count`` is exact.

Numerics contract: a cached page holds bit-identical KV to what the
admitted request's own prefill would have written — chunked prefill
writes the same values as one-shot prefill (the PR 2 serving contract),
and KV at position p depends only on tokens 0..p, which match by
construction of the trie path.  ``serve`` with the cache on therefore
stays token-identical to per-request ``generate`` — cold, warm,
COW-forked, under eviction pressure, and on quantized packs
(tests/test_serving.py, tests/test_prefix_cache.py).
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.obs import spans as _spans
from repro.runtime import faults as _faults


@dataclasses.dataclass
class PrefixCacheStats:
    """Hit/evict/COW counters surfaced through ``ServeStats.prefix``.

    ``hit_tokens`` counts prompt tokens whose KV was reused (full shared
    pages plus the head of each COW fork) — the prefill work the cache
    deleted; ``cached_pages`` snapshots the index size at run end."""
    lookups: int = 0
    hits: int = 0
    misses: int = 0
    hit_tokens: int = 0
    cow_forks: int = 0
    inserted_pages: int = 0
    evicted_pages: int = 0
    cached_pages: int = 0

    @property
    def hit_rate(self) -> float:
        return self.hits / max(self.lookups, 1)


@dataclasses.dataclass
class PrefixHit:
    """A lookup result: ``nodes`` are the full-page matches (their pages
    install verbatim), ``fork_node``/``fork_reuse`` the divergence-page
    COW candidate (reuse the first ``fork_reuse`` positions of that
    page), ``tokens`` the total prompt positions covered."""
    nodes: list
    fork_node: "object | None"
    fork_reuse: int
    tokens: int

    @property
    def pages(self) -> list[int]:
        return [n.page for n in self.nodes]


class _Node:
    __slots__ = ("run", "page", "parent", "children", "last_used")

    def __init__(self, run, page, parent):
        self.run = run                # tuple of page_size token ids
        self.page = page              # physical page id in the pool
        self.parent = parent
        self.children: dict[tuple, _Node] = {}
        self.last_used = 0


class PrefixCache:
    """Radix/trie prefix index over one :class:`PagedKVCache`.

    The cache registers itself as the pool's pressure evictor; all
    mutation happens host-side between device steps, like the allocator
    it extends.
    """

    def __init__(self, pool, *, page_size: int | None = None):
        self.pool = pool
        self.page_size = (page_size if page_size is not None
                          else pool.page_size)
        if self.page_size != pool.page_size:
            raise ValueError(
                f"prefix cache page_size={self.page_size} must match "
                f"the pool's {pool.page_size}")
        self.root = _Node(run=None, page=-1, parent=None)
        self.stats = PrefixCacheStats()
        self._clock = 0
        pool.set_evictor(self._evict)

    # ------------------------------------------------------------ lookup
    def lookup(self, tokens) -> PrefixHit:
        """Longest cached prefix of ``tokens``, capped at
        ``len(tokens) - 1`` positions (the last prompt token is always
        recomputed — its logits seed generation).  Pure: no refcount,
        LRU or pool mutation."""
        # chaos point: fires before any walk — the scheduler degrades a
        # failed lookup to cold prefill (full footprint, no install)
        _faults.maybe_fire("prefix_cache", op="lookup")
        tokens = np.asarray(tokens).reshape(-1)
        with _spans.span("prefix_lookup", prompt=len(tokens)) as sp:
            P = self.page_size
            limit = len(tokens) - 1
            node, nodes, pos = self.root, [], 0
            while pos + P <= limit:
                child = node.children.get(self._run(tokens, pos))
                if child is None:
                    break
                nodes.append(child)
                node = child
                pos += P
            # divergence page: the deepest frontier child sharing the
            # longest head with the remaining tokens is the COW candidate
            fork, reuse = None, 0
            want = tuple(int(t) for t in tokens[pos:min(pos + P, limit)])
            if want:
                for run, child in node.children.items():
                    r = 0
                    for a, b in zip(run, want):
                        if a != b:
                            break
                        r += 1
                    if r > reuse:
                        fork, reuse = child, r
            sp.set(hit_tokens=pos + reuse, pages=len(nodes),
                   cow=fork is not None and reuse > 0)
            return PrefixHit(nodes=nodes, fork_node=fork, fork_reuse=reuse,
                             tokens=pos + reuse)

    def _run(self, tokens, pos) -> tuple:
        return tuple(int(t) for t in tokens[pos:pos + self.page_size])

    # ------------------------------------------------------------- admit
    def admit(self, slot: int, tokens, hit: PrefixHit | None = None) -> int:
        """Install the longest cached prefix of ``tokens`` into
        ``slot``'s (empty) page table: shared pages by reference, the
        divergence page by COW fork.  Returns the number of prompt
        positions covered — the scheduler sets the slot's length there
        and starts chunked prefill at the first uncovered token."""
        # chaos point: fires before the install — a failed admit leaves
        # the slot empty and the scheduler prefills cold (any partial
        # install from a deeper failure is freed by the scheduler)
        _faults.maybe_fire("prefix_cache", op="admit", slot=slot)
        if hit is None:
            hit = self.lookup(tokens)
        self.stats.lookups += 1
        if hit.tokens == 0:
            self.stats.misses += 1
            return 0
        with _spans.span("prefix_admit", slot=slot,
                         hit_tokens=hit.tokens, pages=len(hit.nodes)):
            self.pool.install(slot, hit.pages)
            if hit.fork_node is not None and hit.fork_reuse > 0:
                self.pool.fork(slot, hit.fork_node.page)
                self.stats.cow_forks += 1
                self._touch(hit.fork_node)
            for n in hit.nodes:
                self._touch(n)
        self.stats.hits += 1
        self.stats.hit_tokens += hit.tokens
        return hit.tokens

    # ------------------------------------------------------------ insert
    def insert(self, slot: int, tokens) -> int:
        """Index ``slot``'s full prompt pages once its prompt is fully
        prefilled.  Runs already cached keep their existing page (a
        racing cold duplicate stays private and is freed normally);
        new runs register the slot's own page via ``mark_cached``.
        Returns the number of pages newly indexed."""
        # chaos point: a failed insert only loses future hits — the
        # request's own pages stay private and are freed normally
        _faults.maybe_fire("prefix_cache", op="insert", slot=slot)
        tokens = np.asarray(tokens).reshape(-1)
        with _spans.span("prefix_insert", slot=slot,
                         prompt=len(tokens)) as sp:
            P = self.page_size
            node, added = self.root, 0
            for j in range(len(tokens) // P):
                run = self._run(tokens, j * P)
                child = node.children.get(run)
                if child is None:
                    page = int(self.pool.page_table[slot, j])
                    if page < 0:
                        raise ValueError(
                            f"insert: slot {slot} has no page for prompt "
                            f"run {j} — prompt not fully prefilled?")
                    if self.pool.is_cached(page):
                        # the page already indexes another run, so it
                        # does not hold this prompt's KV: index nothing
                        # from here on (one page, one trie node)
                        break
                    child = _Node(run=run, page=page, parent=node)
                    node.children[run] = child
                    self.pool.mark_cached([page])
                    added += 1
                self._touch(child)
                node = child
            sp.set(added_pages=added)
        self.stats.inserted_pages += added
        return added

    # ---------------------------------------------------------- eviction
    def _evict(self, need: int) -> int:
        """Pool-pressure hook: uncache least-recently-touched
        refcount-0 leaves (cascading as parents become leaves) until
        ``need`` pages came back to the free list or nothing is
        evictable."""
        with _spans.span("prefix_evict", need=need) as sp:
            freed = 0
            while freed < need:
                victim = None
                stack = list(self.root.children.values())
                while stack:
                    n = stack.pop()
                    if n.children:
                        stack.extend(n.children.values())
                    elif self.pool.refcount[n.page] == 0 and (
                            victim is None
                            or n.last_used < victim.last_used):
                        victim = n
                if victim is None:
                    break
                victim.parent.children.pop(victim.run)
                freed += len(self.pool.uncache([victim.page]))
                self.stats.evicted_pages += 1
            sp.set(freed=freed)
            return freed

    def clear(self) -> int:
        """Drop the whole index, returning idle pages to the free list."""
        pages = [n.page for n in self._walk()]
        self.root.children.clear()
        return len(self.pool.uncache(pages))

    # ------------------------------------------------------------- misc
    def _touch(self, node: _Node) -> None:
        """LRU clock: touch ``node`` and its ancestors (ancestors must
        never look colder than a descendant the sweep has to reach
        through them)."""
        self._clock += 1
        while node is not None and node is not self.root:
            node.last_used = self._clock
            node = node.parent

    def _walk(self):
        stack = list(self.root.children.values())
        while stack:
            n = stack.pop()
            stack.extend(n.children.values())
            yield n

    @property
    def num_pages(self) -> int:
        """Pages currently indexed."""
        return sum(1 for _ in self._walk())

    def snapshot_stats(self) -> PrefixCacheStats:
        self.stats.cached_pages = self.num_pages
        return self.stats
