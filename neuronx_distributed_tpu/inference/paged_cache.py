"""Paged KV cache: host-side page allocator + radix prefix index for the
serving engine (PagedAttention, Kwon et al. 2023; RadixAttention, Zheng et
al. 2024 — PAPERS.md serving rows).

Device layout (models/llama.py, decode-attention paged branch): each layer
holds a K and a V page POOL of ``page_pool_pages`` pages x ``page_size``
tokens instead of a ``max_batch x max_seq_len`` slab; a per-slot block table
``(max_batch, max_seq_len/page_size)`` of physical page ids rides the flax
``cache`` collection, so every compiled serving program — right-sized
insert, step decode, the fused K-step session scan — keeps its signature
and its one-dispatch-per-K-tokens contract. Attention resolves logical slot
positions through an in-scan gather of the pool; stale bytes in reused
pages sit behind the position mask exactly like the slab's unwritten zeros,
which is what makes paged attention bit-identical to the contiguous oracle.

Host layout (this module):

* :class:`PageAllocator` — free-list + per-page refcounts. A page is
  returned to the free list when its last holder (active slot or prefix
  cache) releases it.
* :class:`RadixPrefixIndex` — a trie over PROMPT pages: each node is one
  page whose ``page_size`` tokens AND full prefix match the path from the
  root, holding the physical page whose K/V encode exactly that prefix.
  Lookup returns the longest page-aligned cached prefix; admission then
  skips prefill of the shared pages entirely (insert cost O(suffix)).
  Cache-only pages are evicted LRU-leaf-first under pool pressure.
* :class:`PagedKVCache` — per-session bookkeeping: block tables, per-slot
  scratch pages, the plan/commit/rollback/release lifecycle that
  ``CausalLM.insert``/``retire`` drive.
* :class:`HostPageTier` — the host-memory KV tier (Mooncake-style tiering;
  CacheGen's "restore beats recompute" economics): under pool pressure,
  cold cache-only prefix pages are SPILLED — their K/V bytes copied into
  pinned host buffers with a per-page checksum, the radix entry retained
  and marked tiered — instead of dropped. A later prefix hit on a tiered
  path RESTORES the bytes into fresh device pages (checksum-verified)
  before admission, so the prefix cache is host-RAM-bounded instead of
  HBM-bounded. The degradation ladder under pressure is
  spill → restore-what-fits → re-prefill → shed: a restore that fails
  (seeded fault, corrupted tier bytes caught by checksum) invalidates the
  subtree and falls back to re-prefilling the suffix — never a wrong
  token. The tier is INCLUSIVE: a restored page keeps its host copy, which
  doubles as a recovery source when the DEVICE page is later corrupted
  (repair-in-place instead of a replay re-prefill).

Sharing is copy-on-write by construction rather than by copying: shared
pages cover only FULL pages strictly below a request's private region (the
last prompt token always stays in the suffix, so the divergence page is
recomputed privately), and every write — suffix prefill, decode, padding
garbage — lands in privately owned or scratch pages. A shared page is
therefore immutable until its refcount drains to zero.

TP sharding (PR 16): everything in this module is SHARD-AGNOSTIC. Under a
``tp`` mesh the device pools are sharded over the KV-head axis
(``inference/partition.py``), but one LOGICAL page id still maps to one
slice of every shard — block tables, refcounts, the radix trie and the
plan/commit lifecycle all key on logical ids and never see a shard. Only
the byte-accounting callers must pick a basis: per-chip budgets size with
``CausalLM.kv_page_bytes()`` (divided by the TP degree), while the host
tier and KVHandoff payloads hold GLOBAL-width pages (gather-at-seal) and
size with ``kv_page_bytes_host()``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import heapq
import time
import zlib
from collections import deque
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np


class PagePoolExhausted(RuntimeError):
    """Not enough free pages for an admission, even after evicting
    cache-only prefix pages. The scheduler defers the request (pages free up
    as in-flight requests retire)."""


class TierRestoreError(RuntimeError):
    """A host-tier page read failed (injected IO fault). The entry is
    dropped and admission degrades to re-prefilling the suffix."""


class TierCorruption(RuntimeError):
    """A host-tier page's bytes no longer match its stored checksum — the
    copy is poison and is dropped; admission re-prefills instead. The
    checksum is what turns 'corrupted tier bytes' from a wrong-token hazard
    into a latency event."""


class HostPageTier:
    """Host-memory store of spilled KV pages: one entry per radix node,
    holding the page's per-leaf K/V bytes (contiguous host copies — the
    pinned-buffer analogue on this harness) plus a crc32 checksum computed
    at spill time and re-verified on every read. Capacity is bounded in
    PAGES; inserting past it drops the least-recently-used entries (the
    owning index is told via :meth:`put`'s return so it can clear the dead
    radix entries). ``fault_hook`` is the ``tier`` seam of
    ``inference/faults.py``: consulted per :meth:`get`, it may force a
    restore failure or garble the entry's bytes (which the checksum then
    catches) — both deterministic, both ending in re-prefill."""

    def __init__(self, max_pages: int):
        if max_pages < 1:
            raise ValueError(f"host tier needs >= 1 page, got {max_pages}")
        self.max_pages = int(max_pages)
        self._entries: Dict[int, dict] = {}
        self._next = 0
        self._clock = 0
        self.fault_hook: Optional[Callable[[], Optional[str]]] = None
        self.stats = {"puts": 0, "gets": 0, "restore_failures": 0,
                      "checksum_failures": 0, "lru_drops": 0}

    def __len__(self) -> int:
        return len(self._entries)

    def bytes_used(self) -> int:
        return sum(e["nbytes"] for e in self._entries.values())

    @staticmethod
    def _crc(data: Dict[str, np.ndarray]) -> int:
        crc = 0
        for k in sorted(data):
            crc = zlib.crc32(np.ascontiguousarray(data[k]).tobytes(), crc)
        return crc

    def put(self, data: Dict[str, np.ndarray]) -> Tuple[int, List[int]]:
        """Store one page's leaf bytes; returns (tier id, LRU-dropped tier
        ids) — the caller must clear the dropped ids' radix entries."""
        data = {k: np.ascontiguousarray(v) for k, v in data.items()}
        tid = self._next
        self._next += 1
        self._clock += 1
        self._entries[tid] = {
            "data": data, "crc": self._crc(data),
            "nbytes": sum(v.nbytes for v in data.values()),
            "last_used": self._clock,
        }
        self.stats["puts"] += 1
        evicted: List[int] = []
        while len(self._entries) > self.max_pages:
            victim = min((t for t in self._entries if t != tid),
                         key=lambda t: self._entries[t]["last_used"])
            del self._entries[victim]
            evicted.append(victim)
            self.stats["lru_drops"] += 1
        return tid, evicted

    def get(self, tid: int) -> Dict[str, np.ndarray]:
        """Checksum-verified read. Raises :class:`TierRestoreError` /
        :class:`TierCorruption` (entry dropped either way — a copy that
        failed once must never be trusted again)."""
        entry = self._entries[tid]
        self._clock += 1
        entry["last_used"] = self._clock
        self.stats["gets"] += 1
        verdict = self.fault_hook() if self.fault_hook is not None else None
        if verdict == "fail":
            del self._entries[tid]
            self.stats["restore_failures"] += 1
            raise TierRestoreError(f"injected tier read failure (tid {tid})")
        if verdict == "corrupt":
            # physically garble the host copy — the checksum must catch it
            first = next(iter(sorted(entry["data"])))
            entry["data"][first] = entry["data"][first].copy()
            entry["data"][first].view(np.uint8).reshape(-1)[0] ^= 0xFF
        if self._crc(entry["data"]) != entry["crc"]:
            del self._entries[tid]
            self.stats["checksum_failures"] += 1
            raise TierCorruption(f"tier page {tid} failed checksum")
        return entry["data"]

    def drop(self, tid: Optional[int]) -> None:
        if tid is not None:
            self._entries.pop(tid, None)


class PageAllocator:
    """Free-list page allocator with per-page refcounts. ``reserved`` pages
    at the front of the id space never enter the free list (the per-slot
    scratch pages overrun writes land in)."""

    def __init__(self, num_pages: int, reserved: int = 0):
        if num_pages <= reserved:
            raise ValueError(f"pool of {num_pages} pages <= {reserved} reserved")
        self.num_pages = int(num_pages)
        self.reserved = int(reserved)
        self._free = deque(range(reserved, num_pages))
        self.refcount = np.zeros((num_pages,), np.int32)
        # monotone mutation stamp: bumped on every refcount/free-list
        # change so the prefix index can MEMOIZE its evictable/spillable
        # counts (ROADMAP #18 — those counts are the scheduler's per-
        # admission pool-feasibility probe; recomputing the trie walk per
        # probe was an O(cached pages) scan on the placement hot path)
        self.version = 0
        # fault-injection seam (inference/faults.py): when set, an alloc
        # that WOULD succeed may be forced down the exhausted path —
        # deterministic PagePoolExhausted storms for the chaos tests
        self.fault_hook = None

    def available(self) -> int:
        return len(self._free)

    def in_use(self) -> int:
        return self.num_pages - self.reserved - len(self._free)

    def alloc(self, n: int) -> Optional[List[int]]:
        """n fresh pages at refcount 1, or None when the pool can't cover."""
        if n > len(self._free):
            return None
        if self.fault_hook is not None and self.fault_hook(n):
            return None
        pages = [self._free.popleft() for _ in range(n)]
        for p in pages:
            self.refcount[p] = 1
        self.version += 1
        return pages

    def retain(self, pages: Sequence[int]) -> None:
        for p in pages:
            if self.refcount[p] <= 0:
                raise ValueError(f"retain of free page {p}")
            self.refcount[p] += 1
        if pages:
            self.version += 1

    def release(self, pages: Sequence[int]) -> List[int]:
        """Drop one hold per page; returns the pages that hit refcount 0 and
        went back to the free list."""
        freed = []
        for p in pages:
            if self.refcount[p] <= 0:
                raise ValueError(f"release of free page {p}")
            self.refcount[p] -= 1
            if self.refcount[p] == 0:
                self._free.append(p)
                freed.append(p)
        if pages:
            self.version += 1
        return freed


def _ns_tokens(tokens: Sequence[int], ns: Optional[str]) -> list:
    """Adapter-namespaced radix key stream (ISSUE 12 fix): a prefix's KV
    is a function of (tokens, adapter) — every layer's K/V projections run
    under the request's low-rank correction — so reusing a prefix built
    under one adapter (or the identity base model) for a request pinned to
    another would serve WRONG TOKENS. The trie keys on tuples of stream
    elements, so salting each token with the adapter NAME (names are
    stable; pool slot indices churn with LRU) partitions the trie into
    per-adapter namespaces: same-adapter traffic keeps full radix reuse,
    cross-adapter traffic never matches. ``ns=None`` (the base model, and
    every pre-LoRA call path) is byte-for-byte the historic key stream."""
    if ns is None:
        return list(tokens)
    return [(ns, int(t)) for t in tokens]


class _Node:
    """One cached prompt page. Residency states: ``page >= 0`` — device-
    resident (holds one allocator refcount); ``page < 0`` with a
    ``tier_id`` — spilled to the host tier; ``page < 0`` and no tier id —
    DEAD (dropped from the trie; the marker keeps a stale reference held by
    an in-flight admission plan from resurrecting a freed page). A node may
    be BOTH device-resident and tiered (inclusive tier: a restored page
    keeps its host copy as a corruption-repair source)."""

    __slots__ = ("children", "page", "parent", "key", "last_used", "tier_id",
                 "dead")

    def __init__(self, key, page, parent):
        self.children: Dict[tuple, _Node] = {}
        self.key = key
        self.page = page
        self.parent = parent
        self.last_used = 0
        self.tier_id: Optional[int] = None
        self.dead = False


class RadixPrefixIndex:
    """Page-granular prompt prefix trie. Each cached DEVICE page holds one
    allocator refcount; under pool pressure cache-only pages are spilled to
    the host tier when one is attached (entry retained, marked tiered) and
    dropped otherwise (LRU over leaves)."""

    def __init__(self, page_size: int, allocator: PageAllocator):
        self.page_size = int(page_size)
        self.allocator = allocator
        self.root = _Node(None, -1, None)
        self._clock = 0
        self.cached_pages = 0
        # host tier (attach_tier): None keeps the drop-on-evict behaviour
        self.tier: Optional[HostPageTier] = None
        self._read_page = None      # device page -> {leaf path: np bytes}
        self._tier_nodes: Dict[int, _Node] = {}
        # ROADMAP #18 ordered structures: physical page -> trie node map
        # (corruption repair used to walk the whole trie per probe), a
        # lazy-deleted min-heap over (last_used, seq) for LRU victim
        # selection in spill/evict (was a full-trie scan PER VICTIM), and
        # a version-stamped memo for the evictable/spillable counts the
        # scheduler probes per admission/placement decision
        self._page_node: Dict[int, _Node] = {}
        self._lru: List[Tuple[int, int, _Node]] = []
        self._lru_seq = 0
        self._mut = 0                       # structural mutation stamp
        self._memo_key: Tuple[int, int] = (-1, -1)
        self._memo: Tuple[int, int] = (0, 0)

    def attach_tier(self, tier: HostPageTier, read_page) -> None:
        self.tier = tier
        self._read_page = read_page
        self._mut += 1

    # --- ordered-structure maintenance -----------------------------------

    def _touch(self, node: _Node) -> None:
        """Stamp the node with the current clock and (re)enter it in the
        LRU heap. Path nodes are touched root-first within one walk, and
        the heap tie-breaks equal stamps by push order, so victim
        selection among same-walk nodes keeps the old shallowest-first
        iteration order."""
        node.last_used = self._clock
        self._lru_seq += 1
        heapq.heappush(self._lru, (node.last_used, self._lru_seq, node))
        if len(self._lru) > 64 + 4 * max(self.cached_pages, 1):
            self._compact_lru()

    def _compact_lru(self) -> None:
        seen = set()
        keep = []
        for stamp, seq, node in sorted(self._lru):
            if node.dead or node.last_used != stamp or id(node) in seen:
                continue
            seen.add(id(node))
            keep.append((stamp, seq, node))
        self._lru = keep
        heapq.heapify(self._lru)

    def _set_page(self, node: _Node, page: int) -> None:
        """Single point of truth for a node's device residency: keeps the
        page->node map in sync (the O(1) ``node_for_page``)."""
        if node.page >= 0 and self._page_node.get(node.page) is node:
            del self._page_node[node.page]
        node.page = int(page)
        if page >= 0:
            self._page_node[int(page)] = node
        self._mut += 1

    def _pop_lru_victim(self, candidate) -> Optional[_Node]:
        """Least-recently-used live node satisfying ``candidate`` via the
        lazy heap: dead/stale entries are discarded permanently, valid
        non-candidates (shared pages, already-tiered nodes) are kept
        aside and restored — the pop cost is bounded by the trie size
        (the pool), amortized far below the old full scan per victim."""
        side = []
        found = None
        while self._lru:
            item = heapq.heappop(self._lru)
            stamp, _seq, node = item
            if node.dead or node.last_used != stamp:
                continue
            if candidate(node):
                found = node
                side.append(item)
                break
            side.append(item)
        for item in side:
            heapq.heappush(self._lru, item)
        return found

    def lookup(self, tokens: Sequence[int]) -> List[int]:
        """Physical page ids of the longest DEVICE-RESIDENT cached
        page-aligned prefix of ``tokens`` (possibly empty), LRU-touched
        along the path. Stops at the first tiered entry — admission paths
        that can restore walk :meth:`lookup_nodes` instead."""
        pages = []
        for node in self.lookup_nodes(tokens):
            if node.page < 0:
                break
            pages.append(node.page)
        return pages

    def lookup_nodes(self, tokens: Sequence[int]) -> List[_Node]:
        """Trie nodes of the longest cached page-aligned prefix — device-
        resident AND tiered entries — LRU-touched along the path. The
        tier-aware admission walk: the caller restores tiered nodes (or
        degrades to a shorter prefix)."""
        ps = self.page_size
        self._clock += 1
        node, out = self.root, []
        for i in range(len(tokens) // ps):
            child = node.children.get(tuple(tokens[i * ps:(i + 1) * ps]))
            if child is None:
                break
            self._touch(child)
            out.append(child)
            node = child
        return out

    def peek(self, tokens: Sequence[int]) -> List[int]:
        """Read-only :meth:`lookup_nodes`: page ids of the longest cached
        page-aligned prefix WITHOUT touching the LRU clock, taking any hold,
        or triggering a tier restore — the Router's prefix-affinity probe
        (it peeks every replica per placement; a probe that refreshed LRU
        stamps would let routing queries keep dead prefixes resident).
        Tiered entries report as ``-1`` page ids: a tiered prefix counts as
        a hit (restore is ~a block, re-prefill is the whole suffix), so
        placement prefers replicas whose tier holds the prefix."""
        ps = self.page_size
        node, pages = self.root, []
        for i in range(len(tokens) // ps):
            child = node.children.get(tuple(tokens[i * ps:(i + 1) * ps]))
            if child is None:
                break
            pages.append(child.page if child.page >= 0 else -1)
            node = child
        return pages

    def _counts(self) -> Tuple[int, int]:
        """(evictable, spillable) with a version-stamped memo: the counts
        only change when the allocator's refcounts/free list or the trie
        structure do, so the scheduler's per-admission (and the router's
        per-placement) feasibility probes between mutations are O(1)
        instead of a full trie walk each (ROADMAP #18)."""
        key = (self.allocator.version, self._mut)
        if self._memo_key == key:
            return self._memo

        def count(node) -> Tuple[int, bool]:
            total, all_ev = 0, True
            for c in node.children.values():
                t, ev = count(c)
                total += t
                all_ev = all_ev and ev
            if node.page < 0:
                return total, all_ev
            if all_ev and self.allocator.refcount[node.page] == 1:
                return total + 1, True
            return total, False

        ev = sum(count(c)[0] for c in self.root.children.values())
        sp = 0
        if self.tier is not None:
            sp = sum(1 for n in self._iter_nodes()
                     if n.page >= 0 and self.allocator.refcount[n.page] == 1)
        self._memo_key = key
        self._memo = (ev, sp)
        return self._memo

    def evictable_pages(self) -> int:
        """DEVICE pages LRU eviction could return to the free list right
        now: cache-only (refcount 1) nodes whose whole subtree is also
        evictable (eviction frees leaves first, so a cache-only node above a
        slot-held page stays pinned). Tiered entries hold no device page —
        they count 0 and are transparent (they never pin an ancestor). The
        scheduler's pool-feasibility probe (memoized — see _counts)."""
        return self._counts()[0]

    def spillable_pages(self) -> int:
        """DEVICE pages a spill could move to the host tier right now: ANY
        cache-only node, leaf or interior — spilling keeps the trie entry,
        so interior nodes are fair game (eviction can only drop leaves).
        0 without a tier. Memoized — see _counts."""
        if self.tier is None:
            return 0
        return self._counts()[1]

    def reclaimable_pages(self) -> int:
        """Device pages :meth:`reclaim` could free right now — the
        scheduler's feasibility probe: spillable (tier attached) since
        spillable ⊇ evictable, else evictable."""
        return (self.spillable_pages() if self.tier is not None
                else self.evictable_pages())

    def spill(self, n_pages: int) -> int:
        """Spill up to ``n_pages`` cold cache-only DEVICE pages into the
        host tier (LRU order, interior nodes included): bytes copied out
        with a checksum, the device page released to the free list, the
        radix entry retained and marked tiered. A node that already holds an
        (inclusive) tier copy skips the byte copy. Returns pages freed."""
        if self.tier is None or self._read_page is None:
            return 0
        freed = 0
        while freed < n_pages:
            node = self._pop_lru_victim(
                lambda n: n.page >= 0
                and self.allocator.refcount[n.page] == 1)
            if node is None:
                return freed
            if node.tier_id is None:
                tid, dropped = self.tier.put(self._read_page(node.page))
                node.tier_id = tid
                self._tier_nodes[tid] = node
                for d in dropped:
                    self._on_tier_drop(d)
            if node.page >= 0:
                page = node.page
                self._set_page(node, -1)
                freed += len(self.allocator.release([page]))
            else:
                # a tier-LRU cascade dropped an ancestor whose subtree
                # included this node — its device page was freed there
                freed += 1
        return freed

    def _on_tier_drop(self, tid: int) -> None:
        """The tier LRU-dropped ``tid``: clear the marker; a tiered-ONLY
        node loses its last copy and leaves the trie with its subtree."""
        node = self._tier_nodes.pop(tid, None)
        if node is None:
            return
        node.tier_id = None
        self._mut += 1
        if node.page < 0 and node.key in getattr(node.parent, "children", {}):
            self._drop_subtree(node)
            del node.parent.children[node.key]

    def node_for_page(self, page: int) -> Optional[_Node]:
        """The trie node currently holding device page ``page`` (None when
        the page is request-private) — the corruption-repair probe, O(1)
        off the page->node map."""
        return self._page_node.get(int(page))

    def register(self, tokens: Sequence[int], pages: Sequence[int]) -> None:
        """Record prompt pages AFTER their K/V were written. A page whose
        path already exists as a DEVICE entry keeps that entry (the new
        physical copy stays request-private and is freed at retire); a
        TIERED entry re-adopts the freshly written device page (identical
        content — the re-prefill just repopulated device residency, so the
        next hit skips the restore); new entries take one cache refcount
        hold."""
        ps = self.page_size
        if len(pages) * ps > len(tokens):
            raise ValueError("register: pages exceed token coverage")
        self._clock += 1
        node = self.root
        for i, page in enumerate(pages):
            key = tuple(tokens[i * ps:(i + 1) * ps])
            child = node.children.get(key)
            if child is None:
                child = _Node(key, -1, node)
                node.children[key] = child
                self._set_page(child, int(page))
                self.allocator.retain([int(page)])
                self.cached_pages += 1
            elif child.page < 0:
                self._set_page(child, int(page))
                self.allocator.retain([int(page)])
            self._touch(child)
            node = child

    def evict(self, n_pages: int) -> int:
        """Evict LRU DEVICE-resident leaf entries whose only hold is the
        cache's, until ``n_pages`` pages returned to the free list (or no
        candidate is left). Tiered-only leaves are never victims here —
        they hold no device page, so dropping them frees nothing and would
        destroy exactly the copies the tier exists to keep (use
        :meth:`drop_tiered` for a full drain). Returns the number of device
        pages actually freed."""
        freed = 0
        while freed < n_pages:
            victim = self._pop_lru_victim(
                lambda c: not c.children and c.page >= 0
                and self.allocator.refcount[c.page] == 1)
            if victim is None:
                return freed
            del victim.parent.children[victim.key]
            freed += self._drop_subtree(victim)
        return freed

    def drop_tiered(self) -> int:
        """Drop every tiered-ONLY subtree (host copies included) — the
        full-drain complement to ``evict(10**6)``: call drop_tiered FIRST
        (a tiered-only leaf shields its device ancestors from leaf-first
        eviction), then evict — after both, the trie, the allocator's
        cache holds, AND the tier must all be empty, the no-leak invariant
        the chaos tests pin. Returns entries dropped."""
        dropped = 0

        def scrub(node):
            nonlocal dropped
            for key, child in list(node.children.items()):
                if child.page < 0:
                    before = self.cached_pages
                    self._drop_subtree(child)
                    dropped += before - self.cached_pages
                    del node.children[key]
                else:
                    scrub(child)

        scrub(self.root)
        return dropped

    def invalidate_pages(self, pages: Sequence[int]) -> int:
        """Drop every trie entry whose physical page is in ``pages`` (a
        corrupted-page report), INCLUDING its whole subtree — a descendant's
        prefix runs through the bad page, so a sharer admitted against it
        would splice corrupted K/V into its context. Each removed node's
        cache hold is released and its tier copy dropped (a tier copy of a
        page just declared corrupt may itself be suspect — the repair path
        that trusts one verifies the checksum FIRST and is the only reader
        that may). Returns the number of entries removed."""
        bad = {int(p) for p in pages}
        removed = 0

        def scrub(node):
            nonlocal removed
            for key, child in list(node.children.items()):
                if child.page in bad:
                    before = self.cached_pages
                    self._drop_subtree(child)
                    removed += before - self.cached_pages
                    del node.children[key]
                else:
                    scrub(child)

        scrub(self.root)
        return removed

    def invalidate_tokens(self, tokens: Sequence[int]) -> int:
        """Drop the trie path covering ``tokens`` — subtree included, device
        holds released, tier copies dropped. The park path's residency
        scrub: a conversation evicted to the durable tier must leave no
        device OR host copy behind, and unlike :meth:`invalidate_pages`
        this also reaches entries that are tiered-ONLY (page = -1, so no
        physical-page report could ever name them). Aggressive by design:
        siblings sharing the first page lose their cache entries too (their
        slot holds are untouched — only the cache's copies go), the same
        first-page-subtree blast radius ``invalidate_pages`` already has.
        Returns entries removed."""
        ps = self.page_size
        if len(tokens) < ps:
            return 0        # no full page was ever registered
        # key exactly as register() does: raw stream elements — an
        # adapter-namespaced stream carries (ns, token) tuples, which an
        # int() coercion would reject; plain streams normalize to int
        key = tuple(t if isinstance(t, tuple) else int(t)
                    for t in tokens[:ps])
        child = self.root.children.get(key)
        if child is None:
            return 0
        before = self.cached_pages
        self._drop_subtree(child)
        del self.root.children[key]
        return before - self.cached_pages

    def _drop_subtree(self, node) -> int:
        """Remove ``node`` and its descendants from all accounting: device
        holds released, tier copies dropped, DEAD-marked (page = -1, no
        tier id) so a stale reference held by an in-flight admission plan
        can never resurrect a freed page. Returns device pages freed."""
        freed = 0
        self.cached_pages -= 1
        if node.page >= 0:
            page = node.page
            self._set_page(node, -1)
            freed += len(self.allocator.release([page]))
        if node.tier_id is not None:
            if self.tier is not None:
                self.tier.drop(node.tier_id)
            self._tier_nodes.pop(node.tier_id, None)
        node.page = -1
        node.tier_id = None
        node.dead = True
        self._mut += 1
        for child in node.children.values():
            freed += self._drop_subtree(child)
        return freed

    def _iter_nodes(self):
        stack = list(self.root.children.values())
        while stack:
            node = stack.pop()
            yield node
            stack.extend(node.children.values())


@dataclasses.dataclass
class ChunkedPrefill:
    """In-flight chunked-prefill page state for ONE request (Sarathi-style
    stall-free admission): pages are allocated INCREMENTALLY as chunks
    extend coverage, so a long prompt never has to find its whole footprint
    free at once — and an abort (pool pressure mid-prefill, client cancel)
    rolls every hold back atomically. ``start`` is the page-aligned reused
    prefix length (chunk prefill begins there); ``owned`` grows per
    :meth:`PagedKVCache.extend_chunked` call."""

    tokens: List[int]
    reserve_total: int
    start: int
    shared: List[int]
    owned: List[int] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class InsertPlan:
    """One admission's page layout: ``table`` is the full block-table row
    (shared pages, then owned pages, scratch fill), ``start`` the page-
    aligned length of the reused prefix (suffix prefill begins there)."""

    table: np.ndarray
    start: int
    prompt_len: int
    shared: List[int]
    owned: List[int]


class PagedKVCache:
    """Per-session host state for the paged pool: block tables, scratch
    pages, allocator, prefix index, and the insert/retire lifecycle."""

    def __init__(self, page_size: int, num_pages: int, max_batch: int,
                 max_seq_len: int, prefix_cache: bool = True):
        if max_seq_len % page_size:
            raise ValueError(
                f"page_size {page_size} must divide max_seq_len {max_seq_len}")
        self.page_size = int(page_size)
        self.num_pages = int(num_pages)
        self.max_batch = int(max_batch)
        self.max_seq_len = int(max_seq_len)
        self.pages_per_slot = max_seq_len // page_size
        if num_pages < max_batch + 1:
            # scratch pages + at least one allocatable page; per-request
            # feasibility against the pool is the scheduler's job (the
            # engine validates pages_needed() <= capacity_pages() at submit)
            raise ValueError(
                f"pool of {num_pages} pages cannot hold {max_batch} scratch "
                f"pages + one allocatable page")
        # page i < max_batch is slot i's scratch page: the target of unowned
        # table entries, so overrun/garbage writes never touch live pages
        self.scratch = np.arange(max_batch, dtype=np.int32)
        self.allocator = PageAllocator(num_pages, reserved=max_batch)
        self.prefix: Optional[RadixPrefixIndex] = (
            RadixPrefixIndex(page_size, self.allocator) if prefix_cache else None)
        self.tables = np.tile(self.scratch[:, None],
                              (1, self.pages_per_slot)).astype(np.int32)
        self._slot_pages: Dict[int, List[int]] = {}
        self.stats = {"prefix_queries": 0, "prefix_hits": 0,
                      "prefix_hit_tokens": 0, "evicted_pages": 0,
                      "pages_in_use_peak": 0,
                      # host-tier surface (zeros with the tier disabled)
                      "tier_spilled_pages": 0, "tier_restored_pages": 0,
                      "tier_hits": 0, "tier_restore_failures": 0,
                      "tier_repaired_pages": 0,
                      # prefill/decode disaggregation: pages whose K/V bytes
                      # arrived through a migration handoff (adopt_pages)
                      "adopted_pages": 0}
        # host-memory tier (enable_tier): spilled cold prefix pages +
        # device read/write callbacks into the session's page pools
        self.tier: Optional[HostPageTier] = None
        self._write_page = None
        self._restore_ms: List[float] = []
        # observability (attach_observability): cache-lane trace events +
        # prefix-hit-length histogram; None => zero-cost no-ops
        self._tracer = None
        self._block_fn = None
        self._m_prefix = None
        self._m_restore = None
        self._m_tier_bytes = None

    # --- host tier -------------------------------------------------------

    def enable_tier(self, max_pages: int, read_page, write_page) -> None:
        """Attach a host-memory tier of ``max_pages`` pages. ``read_page``
        (physical page -> {leaf path: host bytes}) and ``write_page``
        (physical page, bytes -> device write) are the session-cache IO the
        spill/restore cycle runs through — the engine supplies closures
        over its session. Requires the prefix index (tiering without a
        radix entry to retain would be an unreachable copy)."""
        if self.prefix is None:
            raise ValueError("host tier requires prefix_cache=True")
        self.tier = HostPageTier(max_pages)
        self._write_page = write_page
        self.prefix.attach_tier(self.tier, read_page)

    def tier_pages(self) -> int:
        return 0 if self.tier is None else len(self.tier)

    def tier_bytes(self) -> int:
        return 0 if self.tier is None else self.tier.bytes_used()

    def _reclaim(self, n: int) -> int:
        """Free ``n`` device pages by the ladder (spill → evict-drop),
        keeping the legacy 'evicted_pages' stat to dropped entries only."""
        if self.prefix is None:
            return 0
        spilled = self.prefix.spill(n)
        if spilled:
            self.stats["tier_spilled_pages"] += spilled
            self._note_tier("tier:spill", pages=spilled)
        dropped = 0
        if spilled < n:
            dropped = self.prefix.evict(n - spilled)
            self.stats["evicted_pages"] += dropped
            self._note_evict(dropped)
        return spilled + dropped

    def _alloc_with_reclaim(self, n: int) -> Optional[List[int]]:
        """Allocate ``n`` pages, reclaiming (spill-then-evict) from the
        prefix cache on a miss. None only when the pool genuinely cannot
        cover — the caller degrades (shorter restored prefix) or raises
        :class:`PagePoolExhausted` (shed, the last resort)."""
        pages = self.allocator.alloc(n)
        if pages is None and self.prefix is not None:
            self._reclaim(n - self.allocator.available())
            pages = self.allocator.alloc(n)
        return pages

    def _restore_node(self, node) -> Optional[int]:
        """Restore one tiered radix entry into a fresh device page:
        checksum-verified host read, page allocated (reclaim allowed),
        bytes written back, entry re-marked device-resident (the alloc's
        refcount-1 IS the cache hold the spill released). Returns the page
        id, or None to degrade — restore budget exhausted (no page even
        after reclaim) leaves the entry tiered for a later hit; a FAILED or
        corrupt read drops the entry's subtree so the admission re-prefills
        (never a wrong token)."""
        if self.tier is None or node.tier_id is None:
            return None
        t0 = time.perf_counter()
        try:
            data = self.tier.get(node.tier_id)
        except (TierRestoreError, TierCorruption) as e:
            self.stats["tier_restore_failures"] += 1
            self._note_tier("tier:corrupt", error=type(e).__name__)
            # the tier already dropped the entry; scrub the trie subtree
            self.prefix._tier_nodes.pop(node.tier_id, None)
            node.tier_id = None
            if node.key in getattr(node.parent, "children", {}):
                self.prefix._drop_subtree(node)
                del node.parent.children[node.key]
            return None
        pages = self._alloc_with_reclaim(1)
        if pages is None:
            self._note_exhausted(1)
            return None
        self._write_page(pages[0], data)
        self.prefix._set_page(node, pages[0])
        dt_ms = (time.perf_counter() - t0) * 1e3
        self._restore_ms.append(dt_ms)
        self.stats["tier_restored_pages"] += 1
        if self._m_restore is not None:
            self._m_restore.observe(dt_ms)
        self._note_tier("tier:restore", page=pages[0],
                        ms=round(dt_ms, 3))
        return pages[0]

    def _resolve_prefix(self, tokens: Sequence[int]) -> List[int]:
        """The tier-aware admission prefix: walk the cached path, retaining
        device pages as they come and restoring tiered entries as the pool
        affords (spill → restore-budget — a restore that cannot get a page
        shortens the reused prefix instead of shedding; the suffix prefill
        covers the rest). Every returned page carries one admission hold —
        release on rollback."""
        if self.prefix is None:
            return []
        ps = self.page_size
        nodes = self.prefix.lookup_nodes(tokens)[: (len(tokens) - 1) // ps]
        shared: List[int] = []
        tiered_used = False
        for node in nodes:
            if node.page >= 0:
                self.allocator.retain([node.page])
            else:
                if self._restore_node(node) is None:
                    break
                tiered_used = True
                self.allocator.retain([node.page])
            shared.append(node.page)
        if tiered_used:
            self.stats["tier_hits"] += 1
        return shared

    def repair_page_from_tier(self, page: int) -> bool:
        """Corrupted DEVICE page whose radix entry still holds an inclusive
        host copy: verify the copy's checksum and write it back over the
        garbled device bytes — the subtree stays valid and no stream
        replays. False (tier absent / page not tiered / copy failed its
        checksum) sends the caller down the invalidate+replay path."""
        if self.tier is None or self.prefix is None:
            return False
        node = self.prefix.node_for_page(int(page))
        if node is None or node.tier_id is None:
            return False
        t0 = time.perf_counter()
        try:
            data = self.tier.get(node.tier_id)
        except (TierRestoreError, TierCorruption) as e:
            self.stats["tier_restore_failures"] += 1
            self._note_tier("tier:corrupt", error=type(e).__name__)
            self.prefix._tier_nodes.pop(node.tier_id, None)
            node.tier_id = None
            return False
        self._write_page(int(page), data)
        dt_ms = (time.perf_counter() - t0) * 1e3
        self._restore_ms.append(dt_ms)
        self.stats["tier_repaired_pages"] += 1
        if self._m_restore is not None:
            self._m_restore.observe(dt_ms)
        self._note_tier("tier:restore", page=int(page), repair=True,
                        ms=round(dt_ms, 3))
        return True

    # --- observability ---------------------------------------------------

    def attach_observability(self, tracer, metrics, block_fn=None) -> None:
        """Wire the serving engine's tracer/registry into the cache seams:
        prefix-hit lengths (histogram + instants), LRU evictions, pool
        exhaustion, and the tier's spill/restore/corrupt lifecycle land on
        the ``cache`` timeline lanes. ``block_fn`` (the engine passes
        ``lambda: self.blocks``) stamps each instant with the virtual block
        so incident trace slices and the attribution layer can window
        cache events on the scheduler clock. Host-side only — nothing here
        can touch a compiled program."""
        self._tracer = tracer
        self._block_fn = block_fn
        self._m_prefix = metrics.histogram(
            "serve_prefix_hit_tokens",
            help="page-aligned prefix tokens reused per admission query",
            lo=1.0)
        self._m_restore = metrics.histogram(
            "serve_tier_restore_ms",
            help="host-tier page restore wall ms (checksum + alloc + copy)",
            lo=0.01)
        self._m_tier_bytes = metrics.gauge(
            "serve_tier_bytes", help="host-tier KV bytes resident")

    def _block(self) -> Optional[int]:
        return None if self._block_fn is None else int(self._block_fn())

    def span(self, name: str, **args):
        """``with pkv.span("cache_plan", rows=8):`` — the body as one host
        span on the ``("cache", "pool")`` lane of the attached tracer,
        numbered by the scheduler's block (``CausalLM._insert_paged`` times
        its ``cache_plan`` and ``cache_commit`` loops with it). Nothing
        attached, or tracing off: a do-nothing context."""
        if self._tracer is None:
            return contextlib.nullcontext()
        return self._tracer.span(name, ("cache", "pool"),
                                 block=self._block(), args=args or None)

    def _note_prefix(self, shared: List[int]) -> None:
        if self._m_prefix is not None:
            self._m_prefix.observe(len(shared) * self.page_size)
        if self._tracer is not None and self._tracer.enabled and shared:
            self._tracer.instant(
                "prefix_hit", ("cache", "pool"), block=self._block(),
                args={"tokens": len(shared) * self.page_size,
                      "pages": len(shared)})

    def _note_evict(self, freed: int) -> None:
        if freed and self._tracer is not None and self._tracer.enabled:
            self._tracer.instant("evict", ("cache", "pool"),
                                 block=self._block(),
                                 args={"pages": int(freed)})

    def _note_exhausted(self, need: int) -> None:
        if self._tracer is not None and self._tracer.enabled:
            self._tracer.instant(
                "pool_exhausted", ("cache", "pool"), block=self._block(),
                args={"need": int(need),
                      "free": int(self.allocator.available())})

    def _note_tier(self, name: str, **args) -> None:
        if self._m_tier_bytes is not None:
            self._m_tier_bytes.set(self.tier_bytes())
        if self._tracer is not None and self._tracer.enabled:
            self._tracer.instant(
                name, ("cache", "tier"), block=self._block(),
                args={**args, "tier_pages": self.tier_pages()})

    # --- admission lifecycle --------------------------------------------

    def plan(self, tokens: Sequence[int], reserve_total: int,
             ns: Optional[str] = None) -> InsertPlan:
        """Plan one admission: longest page-aligned cached prefix (clamped
        below the last prompt token, so suffix prefill is never empty —
        tiered entries are RESTORED into fresh device pages as the pool
        affords) plus freshly allocated pages covering ``reserve_total``
        logical tokens. Under pool pressure the ladder is spill (cold cache
        pages move to the host tier) → restore-budget (the reused prefix
        shortens rather than shed) → evict-drop, and only then
        :class:`PagePoolExhausted`. Holds are taken here — pair every plan
        with :meth:`commit` or :meth:`rollback`. ``ns`` is the request's
        adapter namespace — see :func:`_ns_tokens`; pass the SAME ns to
        the paired :meth:`commit`."""
        ps = self.page_size
        tokens = _ns_tokens(tokens, ns)
        plen = len(tokens)
        if plen < 1:
            raise ValueError("empty prompt")
        shared: List[int] = []
        if self.prefix is not None:
            self.stats["prefix_queries"] += 1
            shared = self._resolve_prefix(tokens)
            if shared:
                self.stats["prefix_hits"] += 1
                self.stats["prefix_hit_tokens"] += len(shared) * ps
            self._note_prefix(shared)
        start = len(shared) * ps
        total = min(max(int(reserve_total), plen), self.max_seq_len)
        n_owned = -(-total // ps) - len(shared)
        # the shared pages already carry this plan's holds (refcount >= 2),
        # so the reclaim inside the alloc below can never free them
        owned = self._alloc_with_reclaim(n_owned)
        if owned is None:
            self.allocator.release(shared)
            self._note_exhausted(n_owned)
            raise PagePoolExhausted(
                f"need {n_owned} pages, {self.allocator.available()} free")
        table = np.empty((self.pages_per_slot,), np.int32)
        table[: len(shared)] = shared
        table[len(shared): len(shared) + n_owned] = owned
        table[len(shared) + n_owned:] = -1   # scratch fill, set at commit
        return InsertPlan(table=table, start=start, prompt_len=plen,
                          shared=list(shared), owned=list(owned))

    def rollback(self, plan: InsertPlan) -> None:
        self.allocator.release(plan.shared)
        self.allocator.release(plan.owned)

    def table_for(self, slot: int, plan: InsertPlan) -> np.ndarray:
        t = plan.table.copy()
        t[t < 0] = self.scratch[slot]
        return t

    def commit(self, slot: int, plan: InsertPlan, tokens: Sequence[int],
               ns: Optional[str] = None) -> None:
        """Install the plan on ``slot`` (releasing whatever it held) and
        register the prompt's fully-covered pages in the prefix index —
        under the same adapter namespace the plan walked."""
        self.release(slot)
        self.tables[slot] = self.table_for(slot, plan)
        self._slot_pages[slot] = plan.shared + plan.owned
        if self.prefix is not None:
            n_full = plan.prompt_len // self.page_size
            self.prefix.register(
                _ns_tokens(tokens, ns)[: n_full * self.page_size],
                [int(p) for p in self.tables[slot, :n_full]])
        self.stats["pages_in_use_peak"] = max(
            self.stats["pages_in_use_peak"], self.allocator.in_use())

    def release(self, slot: int) -> None:
        """Drop the slot's page holds (pages cached in the prefix index stay
        resident until evicted) and point its table back at scratch — a
        retired slot's residual device writes can then never land in a page
        a later request owns (the scatter-isolation analogue)."""
        pages = self._slot_pages.pop(slot, None)
        if pages:
            self.allocator.release(pages)
        self.tables[slot] = self.scratch[slot]

    def purge_conversation(self, slot: int,
                           tokens: Optional[Sequence[int]] = None,
                           ns: Optional[str] = None) -> int:
        """Park-path residency scrub (page export/import BELOW the host
        tier): release the slot's holds AND remove every prefix-index entry
        reachable through its pages or its token path — device copies freed,
        host-tier copies dropped. After this, an idle parked conversation
        holds 0 device and 0 host pages (the acceptance invariant); its only
        copy is the durable one the caller just wrote. The token-path pass
        catches tiered-ONLY entries (page = -1) that a physical-page report
        cannot name. Returns prefix entries removed."""
        pages = [int(p) for p in self._slot_pages.get(slot, ())]
        self.release(slot)
        removed = 0
        if self.prefix is not None:
            if pages:
                removed += self.prefix.invalidate_pages(pages)
            if tokens is not None:
                removed += self.prefix.invalidate_tokens(
                    _ns_tokens(tokens, ns))
        return removed

    def adopt_pages(self, slot: int, tokens: Sequence[int],
                    payloads: Sequence[Dict[str, np.ndarray]], write_pages,
                    reserve_total: int, ns: Optional[str] = None) -> List[int]:
        """Adopt a migrated prompt's KV pages (prefill/decode
        disaggregation, ``inference/disagg.py``): allocate the slot's FULL
        footprint (prompt + decode reserve, reclaim-first like every other
        admission), write the handoff's host bytes into the prompt-covering
        pages through ``write_pages`` (the engine's BATCHED page-IO
        closure: one functional update per K/V leaf for the whole page
        list — the per-page PR 8 transport would copy the pool once per
        page), install the slot's block
        table, and register the prompt's fully-covered pages in the prefix
        index so later admissions on this worker prefix-hit the adopted
        path. The decode-reserve pages hold stale bytes until decode writes
        them — behind the position mask, exactly like a fresh insert's
        unwritten pages. Raises :class:`PagePoolExhausted` with NOTHING
        allocated (the caller defers and retries as streams retire)."""
        ps = self.page_size
        tokens = _ns_tokens(tokens, ns)
        plen = len(tokens)
        if plen < 1:
            raise ValueError("empty prompt")
        n_copy = -(-plen // ps)
        if len(payloads) != n_copy:
            raise ValueError(
                f"{len(payloads)} page payloads for {n_copy} prompt pages")
        total = min(max(int(reserve_total), plen), self.max_seq_len)
        n_pages = -(-total // ps)
        pages = self._alloc_with_reclaim(n_pages)
        if pages is None:
            self._note_exhausted(n_pages)
            raise PagePoolExhausted(
                f"adoption needs {n_pages} pages, "
                f"{self.allocator.available()} free")
        write_pages([int(p) for p in pages[:n_copy]], list(payloads))
        self.release(slot)
        table = np.full((self.pages_per_slot,), self.scratch[slot], np.int32)
        table[:n_pages] = pages
        self.tables[slot] = table
        self._slot_pages[slot] = [int(p) for p in pages]
        if self.prefix is not None:
            n_full = plen // ps
            if n_full:
                self.prefix.register(list(tokens)[: n_full * ps],
                                     [int(p) for p in pages[:n_full]])
        self.stats["pages_in_use_peak"] = max(
            self.stats["pages_in_use_peak"], self.allocator.in_use())
        self.stats["adopted_pages"] += n_copy
        return [int(p) for p in pages]

    # --- chunked-prefill lifecycle (begin/extend/finish/abort) -----------
    # The one-shot plan/commit pair above allocates a request's WHOLE page
    # footprint before any device work; chunked admission instead allocates
    # per chunk, so prefill of a long prompt interleaves with decode blocks
    # without ever holding pages it has not yet written. Every path pairs:
    # begin -> extend* -> finish  |  begin -> extend* -> abort.

    def begin_chunked(self, tokens: Sequence[int], reserve_total: int,
                      ns: Optional[str] = None) -> ChunkedPrefill:
        """Open a chunked admission: prefix walk (the reused pages are
        retained so mid-prefill reclaim cannot free them; tiered entries
        restore as the pool affords — a restore mid-chunked-prefill is just
        an earlier ``start``) but NO owned pages yet — allocation happens
        per chunk in :meth:`extend_chunked`. Cannot raise
        :class:`PagePoolExhausted` (a failed restore only shortens the
        reused prefix). ``ns``: adapter namespace (:func:`_ns_tokens`) —
        the namespaced stream rides ``state.tokens`` so finish registers
        consistently."""
        ps = self.page_size
        tokens = _ns_tokens(tokens, ns)
        plen = len(tokens)
        if plen < 1:
            raise ValueError("empty prompt")
        shared: List[int] = []
        if self.prefix is not None:
            self.stats["prefix_queries"] += 1
            shared = self._resolve_prefix(tokens)
            if shared:
                self.stats["prefix_hits"] += 1
                self.stats["prefix_hit_tokens"] += len(shared) * ps
            self._note_prefix(shared)
        return ChunkedPrefill(tokens=list(tokens),
                              reserve_total=int(reserve_total),
                              start=len(shared) * ps, shared=list(shared))

    def extend_chunked(self, state: ChunkedPrefill, covered_tokens: int,
                       final: bool = False) -> None:
        """Allocate the pages a chunk needs BEFORE its device program runs:
        coverage grows to ``covered_tokens``; the FINAL chunk additionally
        covers the request's decode reserve (so a finished prefill can never
        stall on decode-room pages). Tries LRU eviction of cache-only prefix
        pages first; raises :class:`PagePoolExhausted` with ``state``
        untouched — the caller aborts (atomic rollback) and the scheduler
        retries the whole admission later."""
        ps = self.page_size
        total = min(int(covered_tokens), self.max_seq_len)
        if final:
            total = min(max(state.reserve_total, len(state.tokens)),
                        self.max_seq_len)
        need = -(-total // ps) - len(state.shared) - len(state.owned)
        if need <= 0:
            return
        pages = self._alloc_with_reclaim(need)
        if pages is None:
            self._note_exhausted(need)
            raise PagePoolExhausted(
                f"chunked prefill needs {need} pages, "
                f"{self.allocator.available()} free")
        state.owned.extend(pages)

    def chunk_table(self, slot: int, state: ChunkedPrefill) -> np.ndarray:
        """Block-table row for the NEXT chunk program: pages allocated so
        far, scratch beyond (unwritten positions read garbage behind the
        position mask; pad-tail garbage writes land in scratch or in owned
        pages a later chunk overwrites). NOT installed in ``self.tables``
        until :meth:`finish_chunked` — a neighbour's retire mid-prefill may
        reset the device row to scratch, and the next chunk program simply
        re-installs this table."""
        t = np.full((self.pages_per_slot,), self.scratch[slot], np.int32)
        pages = state.shared + state.owned
        t[: len(pages)] = pages
        return t

    def finish_chunked(self, slot: int, state: ChunkedPrefill) -> None:
        """Install the completed prefill on ``slot`` and register the
        prompt's fully-covered pages in the prefix index (registration is
        deferred to completion so no sharer can ever hit a half-written
        page). Allocation-free — the final :meth:`extend_chunked` already
        covered prompt + reserve — so this cannot fail after device work."""
        self.release(slot)
        self.tables[slot] = self.chunk_table(slot, state)
        self._slot_pages[slot] = state.shared + state.owned
        if self.prefix is not None:
            n_full = len(state.tokens) // self.page_size
            self.prefix.register(
                state.tokens[: n_full * self.page_size],
                [int(p) for p in self.tables[slot, :n_full]])
        self.stats["pages_in_use_peak"] = max(
            self.stats["pages_in_use_peak"], self.allocator.in_use())

    def abort_chunked(self, slot: int, state: ChunkedPrefill) -> None:
        """Atomic rollback of an in-flight chunked prefill: every hold this
        admission took (shared retains + owned allocations) is released and
        the slot's table row points back at scratch, so the caller's device-
        table refresh isolates any residual writes from pages the pool hands
        to someone else. Idempotent."""
        self.allocator.release(state.shared)
        self.allocator.release(state.owned)
        state.shared, state.owned = [], []
        self.tables[slot] = self.scratch[slot]

    # --- introspection ---------------------------------------------------

    def prefix_peek(self, tokens: Sequence[int],
                    ns: Optional[str] = None) -> int:
        """Length in TOKENS of the cached page-aligned prefix an admission
        of ``tokens`` would reuse — WITHOUT admitting: no hold taken, no
        stats counted, no LRU touch (``RadixPrefixIndex.peek``). The
        Router's prefix-affinity placement queries every replica with this
        and sends the request where its prefix is hot. Clamped below the
        last prompt token, exactly like :meth:`plan` — the peek must
        predict the real admission's reuse, not overstate it."""
        if self.prefix is None:
            return 0
        plen = len(tokens)
        if plen < 1:
            return 0
        hit = self.prefix.peek(
            _ns_tokens(tokens, ns))[: (plen - 1) // self.page_size]
        return len(hit) * self.page_size

    def live_pages(self) -> List[int]:
        """Sorted physical ids of every page a LIVE slot currently holds —
        the victim pool for corruption injection (a corrupted slot-held page
        forces a request replay; cache-only pages are merely invalidated)."""
        pages = set()
        for held in self._slot_pages.values():
            pages.update(int(p) for p in held)
        return sorted(pages)

    def slot_pages(self, slot: int) -> List[int]:
        return list(self._slot_pages.get(slot, []))

    # --- sizing ----------------------------------------------------------

    def pages_needed(self, prompt_len: int, new_tokens: int) -> int:
        total = min(prompt_len + new_tokens, self.max_seq_len)
        return -(-total // self.page_size)

    def capacity_pages(self) -> int:
        return self.num_pages - self.max_batch
