"""PagedKVCache: the page pool + block tables behind continuous
batching.

Design (Ragged Paged Attention, arXiv:2604.15464): K/V live in
fixed-size pages inside ONE preallocated device buffer per layer;
each sequence owns a block table (ordered list of page ids) and a true
length. Growing a sequence by one token never reallocates — at worst
it pops one page off the free list. Completion returns the pages in
O(pages). The pool is sized once (``num_pages * page_size`` token
slots) so device memory is a configuration decision, not a runtime
surprise — exactly the property serving under heavy traffic needs.

This class is the HOST-side manager: block tables, lengths, the free
list, slot assignment, admission accounting. The device-side page
buffers (jax arrays, [num_kv_heads, num_pages, page_size, head_dim]
per layer) are owned here too. They live in ``scope`` under the names
the step programs declare them by (``pool_names``): persistable state
that a step's ``kv_cache_write`` rewrites under its own name, so the
executor donates each pool to the step and stores the array the step
returns, the same buffer written in place, back into the scope
(``BoundStep._run_ordered``). Nothing is fed or fetched. A donated
array is deleted at the dispatch, so whoever dispatches a read or a
write of the pools does so under ``pools_locked()``: a reader enqueues
before the step's write or sees the new arrays, never a deleted one.
All bookkeeping methods are called from the engine's single step loop;
``_lock`` protects the metric/probe reader paths (``stats()`` /
``match_len`` from scrape and traffic threads).

Two kinds of state live here. Pages hold what attention layers keep of
a sequence. What recurrent layers keep (a state-space layer's state, a
conv's last inputs) is in no page: ``state={feed name: (shape, dtype)}``
names a pool of per-lane arrays ``[max_seqs, ...]`` that is created
once, is fed to every step and fetched back (``state`` in,
``set_state`` out) and is rewritten whole. ``num_layers`` is then the
count of ATTENTION layers. A lane's state needs no release and no
reset: the step zeroes it in the graph when the lane's row is at
position 0. Prefix sharing, page export and ingest see pages only, so
the engine refuses them for a model with such state.

**Two kinds of attention layer** (``window=WindowKind(...)``): FULL
layers keep every page of a sequence, as above; WINDOW layers attend
the last ``window`` keys only, so they have pools of their own (their
own KV-head count and page count, ``window_k_pages`` / ``window_v_pages``)
and a sequence holds a page there only while a query can still reach
it. The block table a window layer sees is a RING of ``pages_per_seq``
entries (``window_tables``): the page of positions ``p * page_size ..``
lies at entry ``p % pages_per_seq``. ``window_step(slot, start, n)``,
called before every step that takes ``n`` tokens of the sequence from
position ``start``, returns the pages wholly behind ``start - window +
1`` to the window free list and takes fresh ones up to ``start + n -
1``: the device runs steps in the order they were dispatched, so a page
released here is rewritten only by a later step than any that reads it.
The window pools hold ``max_seqs * pages_per_seq`` pages by
construction, so they never run dry and admission counts full pages
only. Keys may be wider than values (``k_dim``): the K pools then take
the split page layout of kernels/ragged_paged_attention.py. Prefix
sharing, page export and ingest see full pages only, so the engine
refuses them for a model with window layers.

**Latent pages** (``latent=True``, a model whose layers are latent
attention, MLA): one entry a token and layer, the normed latent
(``head_dim`` = kv_lora_rank wide) and the rotated rope key, is the
whole of what a sequence keeps, and it is a key ``k_dim`` = kv_lora_rank
+ rope wide whose first ``head_dim`` values are also its value. So a
layer has ONE pool, the K pool in the split layout ``[1, pages,
page_size + k_dim - head_dim, head_dim]`` with ``page_size ==
head_dim`` (kernels/latent_attention.py), and no V pool: ``v_pages`` is
empty and a page costs ``k_dim`` values a token. Tables, lengths and
the free list are the full layers' own; prefix sharing, page export
and ingest know K and V pools, so the engine refuses them here too.

Page 0 is permanently reserved as the JUNK page: idle decode lanes and
batch-padding rows point their tables at it, so their (discarded)
writes can never corrupt a live sequence.

``dtype="int8"`` selects the QUANTIZED pool (ragged engine only): K/V
pages store blockwise-int8 values plus one fp32 scale per
(head, token slot) — the kernels/quant.py block unit with
block = head_dim. A page then costs ~1/3.6 the fp32 bytes
(``page_bytes``), so the same HBM budget holds ~3.6x the pages and
~2x+ the resident sequences — the capacity multiplier
tests/test_ragged.py::test_int8_capacity_arithmetic holds.

**Radix prefix cache** (``prefix_cache=True``, ragged engine only):
every page carries a REFCOUNT, and full (page-aligned) token runs are
published into a prefix TRIE keyed by the exact page_size-token tuple
each page holds. ``acquire(prompt_tokens)`` walks the trie and
attaches the matched prefix pages to the new sequence's block table BY
REFERENCE — the shared prompt prefills once, ever — while the
unmatched suffix gets private pages (copy-on-write is structural: the
engine only ever writes positions >= the sequence length, and growth
always pops FRESH pages, so a full shared page is immutable by
construction). ``release`` decrements and returns a page to the free
list only at refcount zero; pool pressure evicts trie-only leaves
first, LRU, before admission ever backpressures or a live sequence is
preempted. Since int8 scale planes ride the same page indirection,
a shared page is also a shared quantized page — the two capacity
multipliers compose.

Exhaustion is backpressure, not corruption: ``acquire`` /
``allocate_slot`` / ``ensure_capacity`` raise ``PagePoolExhausted``;
the engine responds by delaying admission (queued requests wait for
pages) or by evicting a victim sequence (whose request is re-queued
for re-prefill — greedy decode makes the recomputed continuation
identical).
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

__all__ = ["PagedKVCache", "PagePoolExhausted", "WindowKind",
           "key_page_shape", "window_ring_pages"]


class PagePoolExhausted(RuntimeError):
    """No free pages for the requested growth — admission backpressure
    (or eviction) must resolve it; never an allocation."""


def pool_names(num_layers: int, quantized: bool = False, prefix: str = "",
               values: bool = True):
    """The scope names of the page pools, ``(k, v, k_scales, v_scales)``
    as lists by layer (the scale lists empty for float pools, the V list
    without ``values``: latent pages): what the step programs declare
    (generation/model.py) and what a cache keeps in its scope. ``prefix``
    "w": the window layers' pools."""
    kinds = ("k_pages", "v_pages") + (("k_scales", "v_scales")
                                      if quantized else ())
    names = [[f"gen_{prefix}{kind}_{i}" for i in range(num_layers)]
             for kind in kinds]
    if not values:
        names[1] = []
    return tuple(names) + ([],) * (4 - len(names))


def key_page_shape(page_size: int, k_dim: int, v_dim: int):
    """``[rows, width]`` of one K page of one KV head: ``[page_size,
    k_dim]``, or for keys wider than their values the split layout
    ``[page_size + k_dim - v_dim, v_dim]`` (page_size == v_dim), whose
    minor dimension is whole lane tiles where ``k_dim`` (192) is not."""
    if k_dim == v_dim:
        return (page_size, k_dim)
    if k_dim < v_dim or page_size != v_dim:
        raise ValueError(
            f"keys {k_dim} wide on values {v_dim} wide: the split page "
            f"layout needs k_dim > v_dim == page_size, got page_size "
            f"{page_size}")
    return (page_size + k_dim - v_dim, v_dim)


def window_ring_pages(window: int, chunk: int, page_size: int) -> int:
    """Pages the keys ``start - window + 1 .. start + chunk - 1`` of one
    step can touch: the width of a window layer's ring table."""
    return (window + chunk - 1 + page_size - 2) // page_size + 1


@dataclasses.dataclass(frozen=True)
class WindowKind:
    """The window layers of a model with two kinds of attention layer."""
    num_layers: int
    num_kv_heads: int
    window: int
    pages_per_seq: int          # the ring's width (window_ring_pages)


_SCATTER_JIT = []


def _scatter_pages(bufs, sel, blks):
    """Write page blocks into pool buffers as ONE jitted call: the
    ingest path (disagg page splice) touches 2-4 buffers per layer,
    and un-jitted per-buffer ``at[].set`` dispatch costs multiples of
    a decode step. jax.jit caches per pytree shape, so the
    power-of-two padding upstream bounds the executable set. The
    buffers are donated (outputs pair with them in order, one shape a
    kind), so a splice holds no second copy of the pools."""
    if not _SCATTER_JIT:
        import jax

        def _run(bufs, sel, blks):
            return [b.at[:, sel].set(x.astype(b.dtype))
                    for b, x in zip(bufs, blks)]

        _SCATTER_JIT.append(jax.jit(_run, donate_argnums=0))
    return _SCATTER_JIT[0](bufs, sel, blks)


class _TrieNode:
    """One published page: ``key`` is the exact page_size-token tuple
    the page holds, ``page`` the pool page id. Children extend the
    token run by one more full page. ``last_used`` is a monotonic tick
    (NOT wall time — deterministic LRU under test). ``tenant`` is the
    traffic-tier identity that published the page — the per-tenant
    trie-quota accounting unit."""

    __slots__ = ("key", "page", "parent", "children", "last_used",
                 "tenant")

    def __init__(self, key, page, parent, tenant="default"):
        self.key = key
        self.page = page
        self.parent = parent
        self.children: Dict[tuple, "_TrieNode"] = {}
        self.last_used = 0
        self.tenant = tenant


class PagedKVCache:
    def __init__(self, num_layers: int, num_kv_heads: int, head_dim: int, *,
                 num_pages: int, page_size: int, max_seqs: int,
                 max_pages_per_seq: int, dtype: str = "float32",
                 prefix_cache: bool = False, prefix_min_pages: int = 1,
                 trie_max_pages: int = 0, tenant_quota_pages: int = 0,
                 state: Optional[Dict[str, tuple]] = None, scope=None,
                 k_dim: Optional[int] = None,
                 window: Optional[WindowKind] = None, latent: bool = False):
        if num_pages < 2:
            raise ValueError("num_pages must be >= 2 (page 0 is reserved)")
        if page_size < 1 or max_seqs < 1 or max_pages_per_seq < 1:
            raise ValueError("page_size/max_seqs/max_pages_per_seq >= 1")
        self.num_layers = int(num_layers)
        self.num_kv_heads = int(num_kv_heads)
        self.head_dim = int(head_dim)
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.max_seqs = int(max_seqs)
        self.max_pages_per_seq = int(max_pages_per_seq)
        self.dtype = dtype
        self.quantized = dtype == "int8"
        # keys as wide as values unless told; a second kind of layer
        self.k_dim = int(k_dim or head_dim)
        self.window = window
        # one pool a layer: the K pool's first head_dim values are the V
        self.latent = bool(latent)
        if (window is not None or self.k_dim != self.head_dim) and (
                self.quantized or prefix_cache):
            raise ValueError("window layers and keys wider than values "
                             "take float pages and no prefix cache")
        self.prefix_cache = bool(prefix_cache)
        self.prefix_min_pages = max(1, int(prefix_min_pages))
        self.trie_max_pages = max(0, int(trie_max_pages))
        self.tenant_quota_pages = max(0, int(tenant_quota_pages))
        self._lock = threading.Lock()
        # device pools, one K + one V per layer (lazy: first access
        # allocates, so constructing a cache in a test costs nothing);
        # int8 pools carry fp32 scale planes [KVH, P, ps] alongside.
        # They are variables of ``scope``: the engine hands in a child
        # of the predictor's scope, so two engines over one predictor
        # keep separate pools while weights resolve through the parent
        if scope is None:
            from ..core.executor import Scope

            scope = Scope()
        self.scope = scope
        self._pool_names = pool_names(self.num_layers, self.quantized,
                                      values=not self.latent)
        self._wpool_names = pool_names(
            window.num_layers if window else 0, prefix="w")[:2]
        self._pool_lock = threading.Lock()
        # the second kind of state: what a sequence's recurrent layers
        # carry is in no page. ``state`` names the arrays ({feed name:
        # (shape, dtype)}, first axis the lane for per-lane ones); they
        # are fed to the step and fetched back, rewritten whole. A lane
        # that takes a new sequence is zeroed inside the step (its first
        # row is at position 0), so release() has nothing to do here.
        self._state_spec = dict(state or {})
        self._state: Optional[Dict[str, Any]] = None
        # host bookkeeping
        self.block_tables = np.zeros((max_seqs, max_pages_per_seq), np.int32)
        self.lengths = np.zeros(max_seqs, np.int32)
        self._pages_of: List[List[int]] = [[] for _ in range(max_seqs)]
        self._active = [False] * max_seqs
        # page 0 = junk page, never on the free list
        self._free = list(range(num_pages - 1, 0, -1))
        # refcounts: one per sequence chain holding the page, plus one
        # if the page is trie-resident; a page returns to the free
        # list only at zero
        self._ref = np.zeros(num_pages, np.int64)
        # the prefix trie (radix cache): root holds no page; each
        # child edge is one full page keyed by its exact token tuple
        self._root = _TrieNode(None, None, None)
        self._node_of_page: Dict[int, _TrieNode] = {}
        self._tick = 0
        # per-slot publish cursor: how many leading chain pages are
        # trie-resident, and the node at that depth (walks resume
        # there instead of re-keying from the root every step)
        self._published_of = [0] * max_seqs
        self._pub_node: List[Optional[_TrieNode]] = [None] * max_seqs
        # a sibling published the same token run onto a DIFFERENT page
        # first — this chain stays private from that depth on
        self._pub_dead = [False] * max_seqs
        # window layers: a ring table a lane, the logical pages a lane
        # holds there (logical page -> pool page), a free list of its own
        ring = window.pages_per_seq if window else 0
        self.window_num_pages = max_seqs * ring + 1 if window else 0
        self.window_tables = np.zeros((max_seqs, max(ring, 1)), np.int32)
        self._wpages_of: List[Dict[int, int]] = [{} for _ in range(max_seqs)]
        self._wfree = list(range(self.window_num_pages - 1, 0, -1))
        self.window_pages_recycled_total = 0
        self.evictions_total = 0
        self.allocations_total = 0
        # radix counters (radix_stats -> paddle_generation_radix_*)
        self.prefix_lookups_total = 0
        self.prefix_hits_total = 0
        self.prefix_hit_tokens_total = 0
        self.prefix_requested_tokens_total = 0
        self.cow_forks_total = 0
        self.leaf_evictions_total = 0
        self.published_pages_total = 0
        # disagg splice counters (ingest = pulled from a page store,
        # exported = read back out for spill/streaming)
        self.ingested_pages_total = 0
        self.exported_pages_total = 0
        # per-tenant trie accounting: pages currently resident, leaf
        # evictions forced by the tenant's own quota, and publishes
        # refused because the quota held and nothing was evictable
        self._tenant_pages: Dict[str, int] = {}
        self._tenant_evictions: Dict[str, int] = {}
        self.tenant_quota_rejections_total = 0

    # -- device buffers ------------------------------------------------------
    def _allocated(self) -> bool:
        return self._pool_names[0][0] in self.scope.vars

    def _ensure_buffers(self):
        if not self._allocated():
            self.reset_buffers()

    def reset_buffers(self) -> None:
        """Fresh zero pools: the first allocation, and what is left to
        do after a step that failed on the device took the pools it was
        donated with it (the trie's pages are gone with them)."""
        import jax.numpy as jnp

        shape = (self.num_kv_heads, self.num_pages, self.page_size,
                 self.head_dim)
        n = self.num_layers
        # scale 1.0 everywhere: a junk/unwritten slot dequantizes to
        # 0.0, never to NaN/garbage
        scales = ([[jnp.ones(shape[:3], "float32") for _ in range(n)]
                   for _ in "kv"] if self.quantized else [])
        k_page = key_page_shape(self.page_size, self.k_dim, self.head_dim)
        shapes = (shape[:2] + k_page,) + (shape,) * (not self.latent)
        if self.window is not None:
            w = self.window
            for names, page in zip(self._wpool_names, (k_page, shape[2:])):
                for name in names:
                    self.scope.vars[name] = jnp.zeros(
                        (w.num_kv_heads, self.window_num_pages) + page,
                        self.dtype)
        pools = [[jnp.zeros(shp, self.dtype) for _ in range(n)]
                 for shp in shapes]
        if self.latent:
            pools.append([])            # no V pool
        self.set_buffers(*pools, *scales)

    def pools_locked(self):
        """``with cache.pools_locked():`` around the DISPATCH of whatever
        reads the pool arrays or donates them (a step, an ingest's
        scatter, an export's gathers), the hand-back of the new arrays
        included, never around a wait for the device: the arrays a
        reader finds in the scope are then alive when it enqueues its
        read, and the device runs what was enqueued in order."""
        return self._pool_lock

    def pools_alive(self) -> bool:
        """False once a failed step has consumed the arrays it was
        donated and handed nothing back."""
        return not any(a.is_deleted() for a in (
            self.k_pages + self.v_pages + self.window_k_pages
            + self.window_v_pages))

    def _pools(self, kind: int) -> List[Any]:
        self._ensure_buffers()
        sv = self.scope.vars
        return [sv[n] for n in self._pool_names[kind]]

    @property
    def k_pages(self) -> List[Any]:
        """The live K pools by layer, as the next step will read them."""
        return self._pools(0)

    @property
    def v_pages(self) -> List[Any]:
        return self._pools(1)

    def _window_pools(self, kind: int) -> List[Any]:
        self._ensure_buffers()
        return [self.scope.vars[n] for n in self._wpool_names[kind]]

    @property
    def window_k_pages(self) -> List[Any]:
        """The live K pools of the window layers (empty without any)."""
        return self._window_pools(0)

    @property
    def window_v_pages(self) -> List[Any]:
        return self._window_pools(1)

    @property
    def k_scales(self) -> Optional[List[Any]]:
        return self._pools(2) if self.quantized else None

    @property
    def v_scales(self) -> Optional[List[Any]]:
        return self._pools(3) if self.quantized else None

    def set_buffers(self, k_pages: List[Any], v_pages: List[Any],
                    k_scales: Optional[List[Any]] = None,
                    v_scales: Optional[List[Any]] = None) -> None:
        """Put whole pools (scale planes too for the int8 pool) where
        the next step reads them: into the scope, under the names the
        step programs declare. The steps themselves never call this:
        they rewrite the pools in place and the executor stores what
        they return."""
        if len(k_pages) != self.num_layers or len(v_pages) != len(
                self._pool_names[1]):
            raise ValueError("set_buffers: wrong layer count")
        if self.quantized and (k_scales is None or v_scales is None):
            raise ValueError("set_buffers: int8 pool needs scale planes")
        arrays = (k_pages, v_pages) + ((k_scales, v_scales)
                                       if self.quantized else ())
        for names, arrs in zip(self._pool_names, arrays):
            self.scope.vars.update(zip(names, arrs))
        # one bump for the lot: bound steps re-resolve their state
        self.scope._bump_generation()

    @property
    def state(self) -> Dict[str, Any]:
        """The recurrent-state arrays by feed name (lazy, zeros)."""
        if self._state is None:
            import jax.numpy as jnp

            self._state = {name: jnp.zeros(shape, dtype)
                           for name, (shape, dtype)
                           in self._state_spec.items()}
        return self._state

    def set_state(self, arrays) -> None:
        """Swap in the state a step fetched, in the spec's order."""
        if len(arrays) != len(self._state_spec):
            raise ValueError("set_state: wrong array count")
        self._state = dict(zip(self._state_spec, arrays))

    def reset_state(self) -> None:
        self._state = None

    def state_bytes(self) -> int:
        import jax.numpy as jnp

        return sum(int(np.prod(shape)) * jnp.dtype(dtype).itemsize
                   for shape, dtype in self._state_spec.values())

    @staticmethod
    def page_bytes(num_kv_heads: int, head_dim: int, page_size: int,
                   dtype: str, k_dim: Optional[int] = None,
                   values: bool = True) -> int:
        """HBM bytes ONE page costs per layer (K + V, scale planes
        included for int8) — the capacity arithmetic the int8 bench
        gates its ~2x-resident-sequences claim on. ``k_dim``: the keys'
        width where it is not the values' ``head_dim``; without
        ``values`` (latent pages) the K page alone."""
        slots = num_kv_heads * page_size
        if dtype == "int8":
            return 2 * (slots * head_dim + 4 * slots)   # int8 body + scales
        import jax.numpy as jnp

        return (slots * (head_dim * values + (k_dim or head_dim))
                * jnp.dtype(dtype).itemsize)

    def pool_bytes(self) -> int:
        """Total device bytes of the page pools across layers, both
        kinds."""
        total = (self.num_layers * self.num_pages
                 * self.page_bytes(self.num_kv_heads, self.head_dim,
                                   self.page_size, self.dtype, self.k_dim,
                                   not self.latent))
        if self.window is not None:
            total += (self.window.num_layers * self.window_num_pages
                      * self.page_bytes(self.window.num_kv_heads,
                                        self.head_dim, self.page_size,
                                        self.dtype, self.k_dim))
        return total

    # -- capacity accounting -------------------------------------------------
    def pages_needed(self, n_tokens: int) -> int:
        return -(-max(int(n_tokens), 0) // self.page_size)

    @property
    def usable_pages(self) -> int:
        """Pool capacity available to sequences (junk page excluded)."""
        return self.num_pages - 1

    def free_pages(self) -> int:
        return len(self._free)

    def can_fit_ever(self, n_tokens: int) -> bool:
        """Could a sequence of n_tokens EVER be served by this pool —
        the admission-time sanity check (Overloaded before prefill)."""
        need = self.pages_needed(n_tokens)
        return (need <= self.usable_pages
                and need <= self.max_pages_per_seq
                and n_tokens <= self.max_pages_per_seq * self.page_size)

    def can_allocate(self, n_tokens: int) -> bool:
        return self.pages_needed(n_tokens) <= len(self._free)

    def can_acquire(self, n_tokens: int, prompt=None) -> bool:
        """can_allocate, but counting trie-only pages the allocator
        may legally reclaim (LRU leaf eviction) on top of the free
        list — the admission check under a warm radix cache.

        With ``prompt`` given, trie-only pages on the prompt's OWN
        match path are excluded from the budget: ``acquire`` ATTACHES
        them (refcount 2, no longer evictable) while still popping
        ``n_tokens`` worth of suffix pages, so counting them as
        reclaimable-for-the-suffix double-books exactly the pages a
        store-ingested run just inserted and admits requests the pool
        cannot serve."""
        with self._lock:
            excl = set()
            if prompt is not None:
                excl = {nd.page for nd in self._match_nodes(prompt)
                        if int(self._ref[nd.page]) == 1}
            budget = len(self._free) + sum(
                1 for p in self._node_of_page
                if int(self._ref[p]) == 1 and p not in excl)
        return self.pages_needed(n_tokens) <= budget

    def free_slots(self) -> int:
        return sum(1 for a in self._active if not a)

    # -- the prefix trie (radix cache) ---------------------------------------
    def _touch(self, node: _TrieNode) -> None:
        self._tick += 1
        node.last_used = self._tick

    def _page_key(self, tokens, i: int) -> tuple:
        ps = self.page_size
        return tuple(int(t) for t in tokens[i * ps:(i + 1) * ps])

    def _match_nodes(self, tokens) -> List[_TrieNode]:
        """Trie path for the longest page-aligned prefix of
        ``tokens``, capped so at least one prompt token is left to
        prefill (the step that samples the first output token), and
        floored at prefix_min_pages (shorter matches are not worth the
        shared-page bookkeeping)."""
        if not self.prefix_cache:
            return []
        cap = (len(tokens) - 1) // self.page_size
        nodes: List[_TrieNode] = []
        node = self._root
        for i in range(cap):
            child = node.children.get(self._page_key(tokens, i))
            if child is None:
                break
            nodes.append(child)
            node = child
        if len(nodes) < self.prefix_min_pages:
            return []
        return nodes

    def match_len(self, tokens) -> int:
        """Matched-prefix length IN TOKENS a prompt would get right
        now. Pure peek — no refcount, no LRU touch, no counters — safe
        from the traffic thread (suffix-only TTFT pricing)."""
        with self._lock:
            return len(self._match_nodes(np.asarray(tokens).reshape(-1))) \
                * self.page_size

    @staticmethod
    def _tenant_key(tenant) -> str:
        return str(tenant) if tenant else "default"

    def _evict_leaf_locked(self, tenant: Optional[str] = None) -> bool:
        """Reclaim ONE trie-only page: the least-recently-used leaf
        whose page no live sequence holds (refcount 1 = the trie's own
        reference). Interior nodes and shared pages are never touched
        — evicting them would free nothing and orphan the path. With
        ``tenant`` set only that tenant's leaves are candidates (the
        per-tenant quota recycles the tenant's own pages, never a
        neighbour's)."""
        best: Optional[_TrieNode] = None
        stack = [self._root]
        while stack:
            node = stack.pop()
            for child in node.children.values():
                if child.children:
                    stack.append(child)
                elif (int(self._ref[child.page]) == 1
                      and (tenant is None or child.tenant == tenant)):
                    if best is None or child.last_used < best.last_used:
                        best = child
        if best is None:
            return False
        del best.parent.children[best.key]
        del self._node_of_page[best.page]
        self._ref[best.page] = 0
        self._free.append(best.page)
        self.leaf_evictions_total += 1
        left = self._tenant_pages.get(best.tenant, 0) - 1
        if left > 0:
            self._tenant_pages[best.tenant] = left
        else:
            self._tenant_pages.pop(best.tenant, None)
        if tenant is not None:
            self._tenant_evictions[tenant] = \
                self._tenant_evictions.get(tenant, 0) + 1
        return True

    def _pop_page_locked(self) -> int:
        """One page off the free list; a dry list reclaims trie-only
        leaves (LRU) BEFORE surfacing backpressure — cached prefixes
        yield to live sequences, never the other way around."""
        if not self._free and not self._evict_leaf_locked():
            raise PagePoolExhausted("page pool dry (no evictable "
                                    "trie leaves)")
        return self._free.pop()

    def _quota_room_locked(self, tenant: str) -> bool:
        """True once ``tenant`` may insert one more trie page: either
        under its quota, or an LRU leaf of its OWN was evicted to make
        room. A refusal is counted — the per-tenant rejection gauge."""
        if not self.tenant_quota_pages:
            return True
        if self._tenant_pages.get(tenant, 0) < self.tenant_quota_pages:
            return True
        if self._evict_leaf_locked(tenant=tenant):
            return True
        self.tenant_quota_rejections_total += 1
        return False

    def publish(self, slot: int, context_tokens, tenant=None) -> int:
        """Insert ``slot``'s full pages into the trie so later prompts
        can attach them. ``context_tokens`` must cover the sequence's
        cached context (prompt + emitted); only pages fully covered by
        ``lengths[slot]`` publish — positions past the length may
        still hold rejected-draft garbage, full pages below it are
        immutable (writes only ever target positions >= length).
        ``tenant`` attributes the new pages for the per-tenant quota.
        Returns the newly published page count. No-op unless
        prefix_cache."""
        if not self.prefix_cache:
            return 0
        tn = self._tenant_key(tenant)
        with self._lock:
            if not self._active[slot] or self._pub_dead[slot]:
                return 0
            tokens = np.asarray(context_tokens).reshape(-1)
            full = min(int(self.lengths[slot]),
                       int(tokens.size)) // self.page_size
            idx = self._published_of[slot]
            if full <= idx:
                return 0
            node = self._pub_node[slot] or self._root
            chain = self._pages_of[slot]
            new = 0
            while idx < full:
                key = self._page_key(tokens, idx)
                child = node.children.get(key)
                if child is not None:
                    if child.page != chain[idx]:
                        # a sibling that cold-prefilled the same run
                        # concurrently published first; keep ours
                        # private rather than re-point live tables
                        self._pub_dead[slot] = True
                        break
                    self._touch(child)
                else:
                    if (self.trie_max_pages
                            and len(self._node_of_page) >= self.trie_max_pages
                            and not self._evict_leaf_locked()):
                        break   # cap reached, nothing evictable: retry later
                    if not self._quota_room_locked(tn):
                        break   # tenant at quota, nothing of theirs to evict
                    child = _TrieNode(key, chain[idx], node, tn)
                    node.children[key] = child
                    self._node_of_page[chain[idx]] = child
                    self._ref[chain[idx]] += 1
                    self._touch(child)
                    self._tenant_pages[tn] = self._tenant_pages.get(tn, 0) + 1
                    new += 1
                node = child
                idx += 1
            self._published_of[slot] = idx
            self._pub_node[slot] = node
            self.published_pages_total += new
            return new

    def drop_trie(self) -> int:
        """Flush the whole prefix trie: every trie-resident page loses
        the trie's reference (freed at zero — shared pages survive
        until their sequences release). Live sequences republish from
        scratch on their next publish. Returns pages freed. The
        teardown/drain hook: after drop_trie + releasing every slot,
        ``pages_in_use`` must be exactly zero."""
        with self._lock:
            freed = 0
            for p in list(self._node_of_page):
                self._ref[p] -= 1
                if self._ref[p] == 0:
                    self._free.append(p)
                    freed += 1
            self._node_of_page.clear()
            self._root.children.clear()
            self._tenant_pages.clear()
            for s in range(self.max_seqs):
                self._published_of[s] = 0
                self._pub_node[s] = self._root if self._active[s] else None
                self._pub_dead[s] = False
            return freed

    def trie_pages(self) -> int:
        with self._lock:
            return len(self._node_of_page)

    def reclaimable_pages(self, slot: int) -> int:
        """Pages that evicting ``slot`` would actually give back: the
        ones only THIS sequence holds (net of the trie's reference —
        a trie-resident page drops to trie-only on release and LRU
        leaf eviction reclaims it on the retry). The engine's pool-dry
        victim ranking uses this instead of raw page count, so a
        mostly-shared sequence is never evicted for ~zero gain."""
        with self._lock:
            return sum(
                1 for p in self._pages_of[slot]
                if int(self._ref[p])
                - (1 if p in self._node_of_page else 0) == 1)

    # -- disagg splice path (page store <-> pool) ----------------------------
    def export_run(self, tokens, max_pages: Optional[int] = None):
        """Read the trie-resident pages along ``tokens``' page-aligned
        prefix out of the device pools, uncapped (a spill wants EVERY
        full page, including the one ``_match_nodes`` reserves for the
        first-output-token prefill). Returns ``(n_pages, k_run, v_run,
        k_scales, v_scales)`` with k/v ``[n, L, KVH, ps, hd]`` in the
        pool dtype and scales ``[n, L, KVH, ps]`` (None for fp32
        pools). Safe against a concurrently running step: full
        trie-resident pages are immutable by construction (writes only
        ever target positions >= length; growth pops fresh pages), and
        the gathers are dispatched under ``pools_locked()``, which the
        step's dispatch holds too: they read the arrays of before the
        step or of after it, never one it has been donated."""
        empty = (0, None, None, None, None)
        if not self.prefix_cache:
            return empty
        tokens = np.asarray(tokens).reshape(-1)
        with self._lock:
            if not self._allocated():
                return empty
            pids: List[int] = []
            node = self._root
            for i in range(int(tokens.size) // self.page_size):
                child = node.children.get(self._page_key(tokens, i))
                if child is None:
                    break
                self._touch(child)
                pids.append(child.page)
                node = child
                if max_pages and len(pids) >= max_pages:
                    break
            self.exported_pages_total += len(pids)
        if not pids:
            return empty
        sel = np.asarray(pids, np.int32)
        with self._pool_lock:
            picked = [[b[:, sel] for b in self._pools(kind)]
                      for kind in range(4 if self.quantized else 2)]
        # the wait for the device, outside the lock
        k_run, v_run = (np.stack([np.asarray(x).transpose(1, 0, 2, 3)
                                  for x in xs], axis=1)
                        for xs in picked[:2])
        k_sc = v_sc = None
        if self.quantized:
            k_sc, v_sc = (np.stack([np.asarray(x).transpose(1, 0, 2)
                                    for x in xs], axis=1)
                          for xs in picked[2:])
        return len(pids), k_run, v_run, k_sc, v_sc

    def ingest_run(self, tokens, k_run, v_run, k_scales=None,
                   v_scales=None, *, tenant=None) -> int:
        """Splice externally-produced full pages (a page-store fetch)
        into the pool + trie so the next ``acquire`` attaches them by
        reference and resumes at ``lengths=matched``. Array layouts
        mirror ``export_run``; data must already be in the POOL dtype
        (int8 pools take int8 bodies + fp32 scale planes verbatim).
        Pages already trie-resident are skipped without a device
        write; caps (``trie_max_pages``, the per-tenant quota, pool
        pressure) truncate the run — a partial ingest just matches
        less, never wrong tokens. MUST be called from the engine's
        step-loop thread (the host bookkeeping is the loop's); the
        scatter donates the pools and hands the new arrays back under
        ``pools_locked()``, as a step does. Returns pages ingested."""
        if not self.prefix_cache:
            return 0
        tokens = np.asarray(tokens).reshape(-1)
        k_run = np.asarray(k_run)
        v_run = np.asarray(v_run)
        n_avail = min(int(tokens.size) // self.page_size,
                      int(k_run.shape[0]), int(v_run.shape[0]))
        if n_avail <= 0:
            return 0
        want = (self.num_layers, self.num_kv_heads, self.page_size,
                self.head_dim)
        if k_run.shape[1:] != want or v_run.shape[1:] != want:
            raise ValueError(
                f"ingest_run: page shape {k_run.shape[1:]} != "
                f"[L,KVH,ps,hd] {want}")
        if self.quantized and (k_scales is None or v_scales is None):
            raise ValueError("ingest_run: int8 pool needs scale planes")
        self._ensure_buffers()
        tn = self._tenant_key(tenant)
        fresh: List[Tuple[int, int]] = []   # (run index, page id)
        with self._lock:
            node = self._root
            for i in range(n_avail):
                key = self._page_key(tokens, i)
                child = node.children.get(key)
                if child is not None:
                    self._touch(child)
                    node = child
                    continue
                if (self.trie_max_pages
                        and len(self._node_of_page) >= self.trie_max_pages
                        and not self._evict_leaf_locked()):
                    break
                if not self._quota_room_locked(tn):
                    break
                try:
                    p = self._pop_page_locked()
                except PagePoolExhausted:
                    break   # partial ingest: shorter match, never wrong
                child = _TrieNode(key, p, node, tn)
                node.children[key] = child
                self._node_of_page[p] = child
                self._ref[p] = 1
                self._touch(child)
                self._tenant_pages[tn] = self._tenant_pages.get(tn, 0) + 1
                fresh.append((i, p))
                node = child
            self.ingested_pages_total += len(fresh)
        if not fresh:
            return 0
        # one fused jitted scatter for every buffer, padded to the next
        # power of two with junk page 0 (block 0 data, harmless): an
        # unbucketed length would compile a fresh executable per
        # distinct run size, and per-buffer at[].set dispatch alone
        # costs multiples of a decode step — both are splice-time
        # stalls on exactly the latency-critical warm-start path
        n = len(fresh)
        width = 1
        while width < n:
            width *= 2
        sel = np.zeros(width, np.int32)
        sel[:n] = [p for _, p in fresh]
        idx = [i for i, _ in fresh] + [fresh[0][0]] * (width - n)
        runs = [k_run, v_run] + ([np.asarray(k_scales), np.asarray(v_scales)]
                                 if self.quantized else [])
        per = len(runs)
        blks = [np.stack([run[i, li] for i in idx], axis=1)
                for li in range(self.num_layers) for run in runs]
        with self._pool_lock:
            pools = [self._pools(kind) for kind in range(per)]
            out = _scatter_pages(
                [pools[kind][li] for li in range(self.num_layers)
                 for kind in range(per)], sel, blks)
            self.set_buffers(*(out[kind::per] for kind in range(per)))
        return len(fresh)

    def trie_leaf_runs(self) -> List[np.ndarray]:
        """Token runs (root-to-leaf concatenated page keys) covering
        every trie leaf — the drain-spill walk: exporting each run
        spills the whole trie with shared interior pages read once per
        leaf path."""
        with self._lock:
            runs: List[np.ndarray] = []
            stack: List[Tuple[_TrieNode, List[int]]] = [(self._root, [])]
            while stack:
                node, path = stack.pop()
                if node is not self._root:
                    path = path + list(node.key)
                if node.children:
                    for child in node.children.values():
                        stack.append((child, path))
                elif path:
                    runs.append(np.asarray(path, np.int64))
            return runs

    # -- sequence lifecycle --------------------------------------------------
    def acquire(self, prompt_tokens) -> Tuple[int, int]:
        """Claim a batch slot + pages for a prompt, attaching any
        trie-matched prefix pages BY REFERENCE (their K/V is already
        resident — prefill starts at the fork point). Returns
        ``(slot, matched_tokens)`` with matched_tokens page-aligned
        and < len(prompt). Raises PagePoolExhausted when slots or
        pages are unavailable *right now* (backpressure, not
        rejection). With prefix_cache off this is exactly
        ``allocate_slot`` (matched_tokens == 0)."""
        tokens = np.asarray(prompt_tokens).reshape(-1)
        n = int(tokens.size)
        need_total = self.pages_needed(n)
        if need_total > self.max_pages_per_seq:
            raise ValueError(
                f"{n} tokens need {need_total} pages > max_pages_per_seq="
                f"{self.max_pages_per_seq}")
        with self._lock:
            slot = next((i for i, a in enumerate(self._active) if not a),
                        None)
            if slot is None:
                raise PagePoolExhausted("no free decode slots")
            nodes = self._match_nodes(tokens)
            if self.prefix_cache:
                self.prefix_lookups_total += 1
                self.prefix_requested_tokens_total += n
            # bump the matched path FIRST: refcount >= 2 shields those
            # pages from the leaf eviction the suffix allocation below
            # may trigger
            for nd in nodes:
                self._ref[nd.page] += 1
                self._touch(nd)
            priv: List[int] = []
            try:
                for _ in range(need_total - len(nodes)):
                    p = self._pop_page_locked()
                    self._ref[p] = 1
                    priv.append(p)
            except PagePoolExhausted:
                for p in priv:
                    self._ref[p] = 0
                    self._free.append(p)
                for nd in nodes:
                    self._ref[nd.page] -= 1
                raise
            pages = [nd.page for nd in nodes] + priv
            self._pages_of[slot] = pages
            row = self.block_tables[slot]
            row[:] = 0
            row[:len(pages)] = pages
            # the matched prefix's K/V is genuinely resident: the new
            # sequence starts at length = matched (the fork point)
            self.lengths[slot] = len(nodes) * self.page_size
            self._active[slot] = True
            self.allocations_total += len(priv)
            self._published_of[slot] = len(nodes)
            self._pub_node[slot] = nodes[-1] if nodes else self._root
            self._pub_dead[slot] = False
            if nodes:
                self.prefix_hits_total += 1
                self.prefix_hit_tokens_total += len(nodes) * self.page_size
                # the first private page past the shared prefix IS the
                # copy-on-write fork
                self.cow_forks_total += 1
            return slot, len(nodes) * self.page_size

    def allocate_slot(self, n_tokens: int) -> int:
        """Claim a batch slot + pages for an n_tokens prompt with NO
        trie consultation (the pre-radix API; warmup and token-count
        callers). Returns the slot id; raises PagePoolExhausted when
        pages or slots are unavailable *right now*."""
        need = self.pages_needed(n_tokens)
        if need > self.max_pages_per_seq:
            raise ValueError(
                f"{n_tokens} tokens need {need} pages > max_pages_per_seq="
                f"{self.max_pages_per_seq}")
        with self._lock:
            slot = next((i for i, a in enumerate(self._active) if not a),
                        None)
            if slot is None:
                raise PagePoolExhausted("no free decode slots")
            pages: List[int] = []
            try:
                for _ in range(need):
                    p = self._pop_page_locked()
                    self._ref[p] = 1
                    pages.append(p)
            except PagePoolExhausted:
                for p in pages:
                    self._ref[p] = 0
                    self._free.append(p)
                raise
            self._pages_of[slot] = pages
            row = self.block_tables[slot]
            row[:] = 0
            row[:len(pages)] = pages
            self.lengths[slot] = 0
            self._active[slot] = True
            self.allocations_total += need
            self._published_of[slot] = 0
            self._pub_node[slot] = self._root
            self._pub_dead[slot] = False
            return slot

    def ensure_capacity(self, slot: int, new_len: int) -> None:
        """Grow slot's page chain to cover new_len tokens; growth pops
        FRESH private pages (never a shared one — that is what makes
        copy-on-write structural); raises PagePoolExhausted when the
        pool is dry even after trie-leaf reclaim (engine evicts
        then)."""
        need = self.pages_needed(new_len)
        if new_len > self.max_pages_per_seq * self.page_size:
            raise ValueError(
                f"sequence of {new_len} tokens exceeds max_pages_per_seq="
                f"{self.max_pages_per_seq} x page_size={self.page_size}")
        with self._lock:
            pages = self._pages_of[slot]
            while len(pages) < need:
                p = self._pop_page_locked()
                self._ref[p] = 1
                self.block_tables[slot, len(pages)] = p
                pages.append(p)
                self.allocations_total += 1

    def window_step(self, slot: int, start: int, n: int) -> None:
        """Before a step takes ``n`` tokens of ``slot`` from position
        ``start``: the window layers' pages wholly behind ``start -
        window + 1`` go back to the free list (no later query reaches
        them), and fresh ones cover the positions up to ``start + n -
        1``. Never dry: the pools hold a full ring a lane."""
        w = self.window
        if w is None or n <= 0:
            return
        ps, ring = self.page_size, w.pages_per_seq
        first = max(start - w.window + 1, 0) // ps
        last = (start + n - 1) // ps
        if last - first >= ring:
            raise ValueError(
                f"a step of {n} tokens at {start} spans {last - first + 1} "
                f"window pages > the ring's {ring}")
        with self._lock:
            held, row = self._wpages_of[slot], self.window_tables[slot]
            for p in [p for p in held if p < first]:
                self._wfree.append(held.pop(p))
                row[p % ring] = 0
                self.window_pages_recycled_total += 1
            for p in range(first, last + 1):
                if p not in held:
                    held[p] = self._wfree.pop()
                    row[p % ring] = held[p]

    def _release_window_locked(self, slot: int) -> None:
        self._wfree.extend(self._wpages_of[slot].values())
        self._wpages_of[slot] = {}
        self.window_tables[slot, :] = 0

    def advance(self, slot: int, n: int = 1) -> int:
        self.lengths[slot] += n
        return int(self.lengths[slot])

    def release(self, slot: int) -> None:
        """Sequence done: every chain page drops one reference; pages
        reach the free list only at refcount ZERO — a page the trie
        (or a sibling sequence) still holds survives. Table row back
        to the junk page, slot reusable."""
        with self._lock:
            for p in self._pages_of[slot]:
                self._ref[p] -= 1
                if self._ref[p] == 0:
                    self._free.append(p)
            self._pages_of[slot] = []
            self.block_tables[slot, :] = 0
            self.lengths[slot] = 0
            self._active[slot] = False
            self._published_of[slot] = 0
            self._pub_node[slot] = None
            self._pub_dead[slot] = False
            self._release_window_locked(slot)

    def evict(self, slot: int) -> None:
        """Preemption: identical to release, but counted — the engine
        re-queues the victim's request for re-prefill."""
        self.release(slot)
        with self._lock:
            self.evictions_total += 1

    def is_active(self, slot: int) -> bool:
        return self._active[slot]

    def active_slots(self) -> List[int]:
        return [i for i, a in enumerate(self._active) if a]

    # -- introspection -------------------------------------------------------
    def stats(self) -> Dict[str, Any]:
        with self._lock:
            in_use = self.usable_pages - len(self._free)
            return {
                "pages_total": self.usable_pages,
                "pages_free": len(self._free),
                "pages_in_use": in_use,
                "page_utilization": (round(in_use / self.usable_pages, 4)
                                     if self.usable_pages else 0.0),
                "active_seqs": sum(1 for a in self._active if a),
                "max_seqs": self.max_seqs,
                "evictions_total": self.evictions_total,
                "page_allocations_total": self.allocations_total,
                "pool_bytes": self.pool_bytes(),
                # by kind of layer (window: 0 without window layers)
                "pages_resident_full": in_use,
                "pages_resident_window": sum(
                    len(h) for h in self._wpages_of),
                "window_pages_total": max(self.window_num_pages - 1, 0),
                "window_pages_recycled_total":
                    self.window_pages_recycled_total,
            }

    def radix_stats(self) -> Dict[str, Any]:
        """The ``paddle_generation_radix_*`` gauge family (nested into
        engine.stats() as the "radix" group): prefix hit volume/rate,
        the shared/private/trie-resident page split, CoW forks and
        trie-leaf evictions."""
        with self._lock:
            chained: Dict[int, int] = {}
            for slot in range(self.max_seqs):
                for p in self._pages_of[slot]:
                    chained[p] = chained.get(p, 0) + 1
            shared = sum(1 for p in chained if int(self._ref[p]) >= 2)
            private = sum(1 for p in chained if int(self._ref[p]) == 1)
            req = self.prefix_requested_tokens_total
            return {
                "enabled": int(self.prefix_cache),
                "prefix_lookups_total": self.prefix_lookups_total,
                "prefix_hits_total": self.prefix_hits_total,
                "prefix_hit_tokens_total": self.prefix_hit_tokens_total,
                "prefix_requested_tokens_total": req,
                "prefix_hit_rate": (
                    round(self.prefix_hit_tokens_total / req, 4)
                    if req else 0.0),
                "shared_pages": shared,
                "private_pages": private,
                "trie_pages": len(self._node_of_page),
                "cow_forks_total": self.cow_forks_total,
                "leaf_evictions_total": self.leaf_evictions_total,
                "published_pages_total": self.published_pages_total,
                "ingested_pages_total": self.ingested_pages_total,
                "exported_pages_total": self.exported_pages_total,
                "tenant_quota_pages": self.tenant_quota_pages,
                "tenant_quota_rejections_total":
                    self.tenant_quota_rejections_total,
                "tenant_pages": dict(self._tenant_pages),
                "tenant_leaf_evictions": dict(self._tenant_evictions),
            }

    def check_integrity(self) -> None:
        """Invariant audit (tests call this after concurrent
        join/leave churn and in every radix-test teardown): chains and
        tables mirror each other, the trie is structurally sound,
        every page's refcount equals (chains holding it) +
        (1 if trie-resident), a page shared by chains is always
        trie-resident, free + in-use covers the pool exactly."""
        with self._lock:
            holders: Dict[int, List[int]] = {}
            for slot in range(self.max_seqs):
                pages = self._pages_of[slot]
                if not self._active[slot] and pages:
                    raise AssertionError(f"inactive slot {slot} holds pages")
                if len(set(pages)) != len(pages):
                    raise AssertionError(
                        f"slot {slot} chain repeats a page: {pages}")
                for j, p in enumerate(pages):
                    if p == 0:
                        raise AssertionError("junk page 0 inside a chain")
                    holders.setdefault(p, []).append(slot)
                    if int(self.block_tables[slot, j]) != p:
                        raise AssertionError(
                            f"table/chain mismatch at slot {slot} idx {j}")
                covered = len(pages) * self.page_size
                if self._active[slot] and int(self.lengths[slot]) > covered:
                    raise AssertionError(
                        f"slot {slot} length {self.lengths[slot]} > "
                        f"allocated {covered}")
            # trie structure: parent/child links coherent, every page
            # appears at most once, node_of_page is exactly the trie
            trie: Dict[int, _TrieNode] = {}
            stack = [self._root]
            while stack:
                node = stack.pop()
                for key, child in node.children.items():
                    if child.parent is not node or child.key != key:
                        raise AssertionError(
                            f"trie link broken at page {child.page}")
                    p = child.page
                    if not isinstance(p, int) or p <= 0:
                        raise AssertionError(f"trie node with bad page {p!r}")
                    if p in trie:
                        raise AssertionError(f"page {p} twice in the trie")
                    if (child.key is not None
                            and len(child.key) != self.page_size):
                        raise AssertionError(
                            f"trie key of {len(child.key)} tokens != "
                            f"page_size {self.page_size}")
                    trie[p] = child
                    stack.append(child)
            if set(trie) != set(self._node_of_page):
                raise AssertionError(
                    "node_of_page desynced from the trie: "
                    f"{set(trie) ^ set(self._node_of_page)}")
            # per-tenant page counts mirror the trie exactly
            tcount: Dict[str, int] = {}
            for nd in trie.values():
                tcount[nd.tenant] = tcount.get(nd.tenant, 0) + 1
            if tcount != self._tenant_pages:
                raise AssertionError(
                    f"tenant page accounting desynced: {tcount} != "
                    f"{self._tenant_pages}")
            for p, nd in trie.items():
                if self._node_of_page[p] is not nd:
                    raise AssertionError(f"node_of_page[{p}] is a stale node")
            # refcounts: chains + trie residency, nothing else
            for p in range(1, self.num_pages):
                expected = len(holders.get(p, ())) + (1 if p in trie else 0)
                if int(self._ref[p]) != expected:
                    raise AssertionError(
                        f"refcount leak: page {p} ref {int(self._ref[p])} "
                        f"!= {expected} (chains {holders.get(p, [])}, "
                        f"trie={p in trie})")
            # a page in two chains got there only via the trie
            for p, slots in holders.items():
                if len(slots) > 1 and p not in trie:
                    raise AssertionError(
                        f"page {p} shared by slots {slots} without trie "
                        "residency")
            # publish cursors stay inside the trie
            for slot in range(self.max_seqs):
                if not self._active[slot]:
                    continue
                pub = self._published_of[slot]
                pages = self._pages_of[slot]
                if pub > len(pages):
                    raise AssertionError(
                        f"slot {slot} published {pub} > chain {len(pages)}")
                for j in range(pub):
                    if pages[j] not in trie:
                        raise AssertionError(
                            f"slot {slot} counts page {pages[j]} as "
                            "published but it is not trie-resident")
            # free list: unique, disjoint from use, refcount zero
            fs = set(self._free)
            if len(fs) != len(self._free):
                raise AssertionError("free list holds duplicates")
            in_use = set(holders) | set(trie)
            dup = fs & in_use
            if dup:
                raise AssertionError(f"pages both free and in use: {dup}")
            bad = [p for p in fs if int(self._ref[p]) != 0]
            if bad:
                raise AssertionError(f"free pages with refs: {bad}")
            if len(fs) + len(in_use) != self.usable_pages:
                raise AssertionError(
                    f"page leak: {len(fs)} free + {len(in_use)} in use "
                    f"!= {self.usable_pages}")
            self._check_window_locked()

    def _check_window_locked(self) -> None:
        """The window kind's invariants: a lane holds at most a ring of
        pages, consecutive, each at its ring entry and nowhere else; no
        page twice; free + held covers the window pool exactly."""
        if self.window is None:
            return
        ring = self.window.pages_per_seq
        seen: Dict[int, int] = {}
        for slot, held in enumerate(self._wpages_of):
            if held and not self._active[slot]:
                raise AssertionError(
                    f"inactive slot {slot} holds window pages")
            if len(held) > ring or (
                    held and max(held) - min(held) >= ring):
                raise AssertionError(
                    f"slot {slot} holds window pages {sorted(held)} past "
                    f"a ring of {ring}")
            row = np.zeros(ring, np.int32)
            for logical, page in held.items():
                if page <= 0 or page in seen:
                    raise AssertionError(
                        f"window page {page} of slot {slot} is the junk "
                        f"page or also slot {seen.get(page)}'s")
                seen[page] = slot
                row[logical % ring] = page
            if not np.array_equal(row, self.window_tables[slot]):
                raise AssertionError(
                    f"window table/ring mismatch at slot {slot}")
        free = set(self._wfree)
        if len(free) != len(self._wfree) or free & set(seen):
            raise AssertionError("window free list repeats or holds a "
                                 "page in use")
        if len(free) + len(seen) != self.window_num_pages - 1:
            raise AssertionError(
                f"window page leak: {len(free)} free + {len(seen)} held "
                f"!= {self.window_num_pages - 1}")
