"""Paged KV storage bookkeeping: refcounted page pool + radix prefix cache.

This module is pure Python — it allocates page *ids* and maps token-id
prefixes to chains of them; the actual KV arrays live on the engine
(`serve/engine.py`), which gathers/scatters pages by index with static
shapes (`models/transformer_lm.gather_pages` / `store_pages`). Keeping the
bookkeeping jax-free is what lets the hypothesis property tests drive
thousands of allocation/eviction orders without compiling a model
(tests/test_serve.py).

Sharing model (copy-on-write at admission granularity):

  * a page holds ``page_size`` consecutive KV positions and is immutable
    once published to the radix tree — readers only ever *gather* it
  * the radix tree maps token-id prefixes (in full-page chunks) to page
    chains; matching a prefix hands back shared page ids, which the engine
    copies into the request's private slot row — that copy IS the "write"
    of copy-on-write, taken eagerly at admission so decode never touches
    shared storage
  * a request extending a shared prefix therefore writes only its private
    row; at retirement its *new* full pages are frozen into freshly
    allocated pages and published, sharing every existing prefix node
  * refcounts: the tree holds one reference per published page; live
    requests pin (incref) their matched chain from admission to retirement
    so eviction can never recycle a page mid-flight. Eviction only
    considers leaf nodes with refcount 1 (tree-only), LRU first. Those
    candidates sit in a min-heap keyed by ``last_used``: a node is pushed
    whenever it becomes a candidate (its last child evicted, its refcount
    back to 1) or is touched while one; an entry that no longer describes
    a candidate at its key is dropped when popped (lazy deletion), and the
    heap is rebuilt from the live candidates when stale entries outgrow
    the tree. An eviction is O(log n) amortised, not a walk of the tree.

KV reusability is exactly prefix-deep: the KV written at position ``i`` is
a pure function of tokens ``0..i`` (per-token activation scales make the
int8 codes row-local; attention at ``i`` only reads positions ``<= i``),
so two requests agreeing on their first ``L`` tokens have bitwise-equal KV
there — the invariance argument in docs/serving.md.
"""
from __future__ import annotations

import dataclasses
import heapq
from typing import Dict, List, Optional, Sequence, Tuple


class PagePool:
    """Refcounted allocator over ``n_pages`` opaque page ids."""

    def __init__(self, n_pages: int):
        if n_pages < 1:
            raise ValueError(f"n_pages must be >= 1, got {n_pages}")
        self.n_pages = n_pages
        # min-heap: the lowest free id is handed out first (deterministic
        # layouts make the aliasing tests exact)
        self._free: List[int] = list(range(n_pages))
        self._ref: List[int] = [0] * n_pages

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def live(self) -> List[int]:
        """Page ids with a nonzero refcount (sorted)."""
        return [p for p in range(self.n_pages) if self._ref[p] > 0]

    def refcount(self, page: int) -> int:
        return self._ref[page]

    def alloc(self) -> Optional[int]:
        """One page at refcount 1, or None when the pool is exhausted."""
        if not self._free:
            return None
        page = heapq.heappop(self._free)
        self._ref[page] = 1
        return page

    def incref(self, page: int) -> None:
        if self._ref[page] <= 0:
            raise RuntimeError(f"incref on free page {page}")
        self._ref[page] += 1

    def decref(self, page: int) -> None:
        if self._ref[page] <= 0:
            raise RuntimeError(f"decref on free page {page}")
        self._ref[page] -= 1
        if self._ref[page] == 0:
            heapq.heappush(self._free, page)


@dataclasses.dataclass(eq=False)
class _Node:
    """One radix-tree edge: a full page of token ids -> its page. ``chunk``
    is this node's key in its parent's ``children`` (the root dict when
    ``parent`` is None)."""
    page: int
    last_used: int
    chunk: Tuple[int, ...]
    parent: Optional["_Node"]
    children: Dict[Tuple[int, ...], "_Node"] = dataclasses.field(
        default_factory=dict)


class PrefixCache:
    """Radix tree over token-id prefixes, full-page granularity.

    ``match`` returns the longest cached chain of full pages; ``insert``
    publishes a finished sequence, allocating pages only for the chunks the
    tree does not already hold (the caller copies the KV for exactly the
    returned assignments). ``match`` runs in O(len(tokens) / page_size)
    dict hops and heap pushes; ``insert`` adds O(log n) amortised per
    evicted page (n = nodes in the tree), popping the LRU evictable leaf
    from the candidate heap. Pins go through ``acquire``/``release``: the
    heap learns of a refcount returning to 1 there, not from the pool.
    """

    # the heap is rebuilt once it holds more than 2 * nodes + this entries
    _HEAP_SLACK = 64

    def __init__(self, page_size: int, n_pages: int):
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        self.page_size = page_size
        self.pool = PagePool(n_pages)
        self._root: Dict[Tuple[int, ...], _Node] = {}
        self._by_page: Dict[int, _Node] = {}   # every node, by its page
        # (last_used, node) for every eviction candidate, plus stale entries
        self._heap: List[Tuple[int, _Node]] = []
        self._clock = 0
        self.hits = 0            # match() calls returning >= 1 page
        self.misses = 0
        self.evictions = 0
        self.stale_pops = 0      # heap entries discarded when popped

    # ---- helpers ---------------------------------------------------------
    def _chunks(self, tokens: Sequence[int]) -> List[Tuple[int, ...]]:
        ps = self.page_size
        toks = [int(t) for t in tokens]
        return [tuple(toks[i:i + ps])
                for i in range(0, len(toks) - len(toks) % ps, ps)]

    def _tick(self) -> int:
        self._clock += 1
        return self._clock

    def _is_candidate(self, node: _Node) -> bool:
        """In the tree, a leaf, and held by the tree alone."""
        return (self._by_page.get(node.page) is node
                and not node.children
                and self.pool.refcount(node.page) == 1)

    def _push(self, node: _Node) -> None:
        """Record ``node`` at its current ``last_used`` if it is a
        candidate; every transition into candidacy calls this."""
        if self._is_candidate(node):
            heapq.heappush(self._heap, (node.last_used, node))
            self._bound_heap()

    def _bound_heap(self) -> None:
        """Rebuild the heap from the live candidates once stale entries
        outgrow the tree: O(n) at most once per ~n pushes or evictions."""
        if len(self._heap) > 2 * len(self._by_page) + self._HEAP_SLACK:
            self._heap = [(n.last_used, n) for n in self._by_page.values()
                          if self._is_candidate(n)]
            heapq.heapify(self._heap)

    @property
    def n_nodes(self) -> int:
        return len(self._by_page)

    def pages(self) -> List[int]:
        """Every page id currently published in the tree (sorted)."""
        return sorted(self._by_page)

    # ---- the cache operations --------------------------------------------
    def match(self, tokens: Sequence[int]) -> List[int]:
        """Longest cached full-page chain covering a prefix of ``tokens``.

        Returns the page ids in order; the caller owns pinning them
        (``acquire``) before gathering. The matched token count is
        ``len(chain) * page_size``.
        """
        chain: List[int] = []
        level = self._root
        for chunk in self._chunks(tokens):
            node = level.get(chunk)
            if node is None:
                break
            node.last_used = self._tick()
            self._push(node)
            chain.append(node.page)
            level = node.children
        if chain:
            self.hits += 1
        else:
            self.misses += 1
        return chain

    def acquire(self, chain: Sequence[int]) -> None:
        """Pin a matched chain for the lifetime of a request."""
        for page in chain:
            self.pool.incref(page)

    def release(self, chain: Sequence[int]) -> None:
        for page in chain:
            self.pool.decref(page)
            self._push(self._by_page[page])

    def insert(self, tokens: Sequence[int]) -> List[Tuple[int, int]]:
        """Publish ``tokens``; returns [(page_id, page_index), ...] for the
        chunks that were newly allocated — the caller must copy positions
        ``[page_index * page_size, (page_index + 1) * page_size)`` of the
        finished sequence into each page. Existing prefix nodes are shared
        untouched. Stops early (keeping the tree prefix-closed) when the
        pool is exhausted and nothing is evictable."""
        new: List[Tuple[int, int]] = []
        pinned: List[_Node] = []
        parent: Optional[_Node] = None
        level = self._root
        for idx, chunk in enumerate(self._chunks(tokens)):
            node = level.get(chunk)
            if node is None:
                page = self._alloc_with_eviction()
                if page is None:
                    break
                node = _Node(page=page, last_used=self._tick(), chunk=chunk,
                             parent=parent)
                level[chunk] = node
                self._by_page[page] = node
                new.append((page, idx))
            else:
                node.last_used = self._tick()
            # pin the path: an eviction triggered by a *later* chunk's
            # allocation must not tear out a node of this very chain (the
            # just-inserted node is a refcount-1 leaf — evicting it would
            # recycle its page into the next chunk and orphan the subtree)
            self.pool.incref(node.page)
            pinned.append(node)
            parent, level = node, node.children
        for node in pinned:
            self.pool.decref(node.page)
            self._push(node)
        return new

    # ---- eviction --------------------------------------------------------
    def _alloc_with_eviction(self) -> Optional[int]:
        page = self.pool.alloc()
        while page is None and self._evict_one():
            page = self.pool.alloc()
        return page

    def _evict_one(self) -> bool:
        """Drop the least-recently-used evictable leaf (refcount 1 — held
        only by the tree; pinned chains of live requests never qualify).
        ``last_used`` is unique per node, so the victim is too."""
        while self._heap:
            last_used, node = heapq.heappop(self._heap)
            if node.last_used == last_used and self._is_candidate(node):
                break
            self.stale_pops += 1
        else:
            return False
        parent = node.parent
        del (self._root if parent is None else parent.children)[node.chunk]
        del self._by_page[node.page]
        self.pool.decref(node.page)
        self.evictions += 1
        if parent is not None:
            self._push(parent)           # may have lost its last child
        self._bound_heap()
        return True

    def stats(self) -> Dict[str, int]:
        return {"nodes": self.n_nodes, "free_pages": self.pool.n_free,
                "hits": self.hits, "misses": self.misses,
                "evictions": self.evictions, "stale_pops": self.stale_pops}
