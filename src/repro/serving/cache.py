"""KV-cache arenas for continuous batching.

Two arena strategies share one engine-facing protocol (``can_admit`` /
``alloc`` / ``touch`` / ``touch_range`` / ``write_slot`` /
``decode_view`` / ``absorb`` / ``release``).  ``decode_view`` /
``absorb`` bracket EVERY device dispatch of the chunked engine — the
unified step fuses decode rows and prefill chunks into one call over
all slot rows (DESIGN.md §Serving ¶Unified attention kernel), so
there is no separate compact prefill view to maintain:

``SlotArena`` — one fixed-shape cache pytree (`n_slots` batch rows x
`max_len` positions) allocated ONCE at engine construction and never
reallocated: every jit'd decode step sees the same shapes, so there is
exactly one decode compilation for the lifetime of the engine.  Slots
are leased to admitted requests and recycled on completion; a slot's
stale contents after release are never visible because per-slot causal
masking (layers/attention._mask with a position *vector*) hides every
position a new tenant has not yet written.  Each lease reserves the
worst-case `max_len` positions regardless of the request's own budget.

``PagedArena`` — the same protocol over a pool of `n_pages`
block-granular pages of `page_size` positions each (DESIGN.md §Serving
¶Paged KV).  Requests lease a decode row (slot) plus a page *budget*
(their own worst case, ceil((P + G - 1) / page_size) pages — not the
arena's), with physical pages allocated on demand as decode advances
and recycled wholesale on completion.  The per-slot page table rides
INSIDE the cache pytree handed to the jit'd decode step, so paging
changes no step-function signature and still compiles exactly once.
Physical page 0 is a trash page: free rows and unallocated logical
blocks map to it, and per-slot masking hides whatever lands there.

Multi-device (DESIGN.md §Serving ¶Multi-device): both arenas take an
optional serving ``mesh`` (+ ``kv_shard``) and are then placed with
explicit NamedShardings — KV leaves split along kv heads over the mesh
"model" axis, page tables / slot metadata / recurrent state replicated
(sharding/rules.arena_leaf_spec).  The ``*_shardings()`` methods expose
the matching pytrees for the engine's explicitly-sharded dispatch jits,
and the arenas' own scatter/gather jits pin the same shardings on
their outputs so the layout survives every engine step.

Prefix caching (DESIGN.md §Prefix-caching): with ``prefix_cache=True``
the PagedArena grows per-page REFCOUNTS, a content-keyed prefix trie
over immutable full pages, and copy-on-write on the first divergent
write.  Admission (`admit_cost` / `can_admit(tokens=...)` /
`alloc(tokens=...)`) charges a request only for its unshared suffix —
shared pages are charged once, to the cache's own ledger — and
`register_prefix` publishes a slot's completed full pages so later
requests with the same token prefix skip their recompute entirely.
Pages whose last reference drops retire WARM (still registered,
refcount 0) under the ``keep_pages`` lazy-eviction budget, which is
what makes a preemption resume re-prefill only its tail.  Everything
is host-side bookkeeping over the existing page pool: the device
layout is untouched, so the kv-head-sharded pools share pages exactly
like the single-device ones.  Integer decode is deterministic
(DESIGN.md §Serving ¶Integer-only invariant), so a cached page is
byte-identical to the recompute it replaces — sharing is exact, not
approximate.

Prefill runs at batch 1 into a scratch cache of identical per-slot
shape, then is scattered into the arena at the leased slot's batch row
(SlotArena) or through the slot's page-table row (PagedArena).  The
batch/sequence axes of each cache leaf are discovered structurally
(the axes whose extents track B and max_len between `eval_shape`
templates), so both arenas work for every cache layout the model zoo
produces: attention KV (n_layers, B, K, T, hd), paired blocks
(n_layers, 2, B, ...), SSM recurrent state (n_layers, B, ...) — which
has no sequence axis and therefore stays slot-resident, unpaged — and
hybrid groups.
"""

from __future__ import annotations

from typing import (
    TYPE_CHECKING,
    Any,
    List,
    Optional,
    Protocol,
    Tuple,
    runtime_checkable,
)

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.rep import Rep

# physical page 0 is the never-allocated trash page (the write helpers
# in layers/attention.py route masked positions there; one definition)
from repro.layers.attention import PAGE_NULL

if TYPE_CHECKING:  # pragma: no cover - import cycle guard
    from repro.serving.config import ServingConfig


def float_cache_leaves(caches) -> List[Tuple[str, Any]]:
    """(path, dtype) of every floating-point leaf in a cache pytree.

    The integer-only serving invariant: an ID-representation run must
    keep KV caches as int8 images.  The single sanctioned exception is
    the SSM recurrent `h` state — the scan float island (DESIGN.md
    §Serving), which is per-slot state, not a KV cache.
    """
    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(caches)[0]:
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            out.append((jax.tree_util.keystr(path), leaf.dtype))
    return out


def assert_integer_caches(caches, *, allow_ssm_state: bool = False):
    """Raise if an ID cache pytree holds float leaves (see above)."""
    bad = float_cache_leaves(caches)
    if allow_ssm_state:
        bad = [(p, d) for p, d in bad if "'h'" not in p]
    if bad:
        raise AssertionError(
            "float leaves in ID serving caches (integer-only invariant "
            f"violated): {bad}"
        )


def map_kv_dicts(tree, fn):
    """Rebuild `tree`, applying fn to every dict holding 'k' and 'v'.

    Attention caches are {'k', 'v'} dicts at every nesting depth the
    model zoo produces; this is the structural hook the paged arena
    uses to thread its page table into (and strip it back out of) the
    cache pytree around each decode step.
    """
    if isinstance(tree, dict):
        if "k" in tree and "v" in tree:
            return fn(tree)
        return {k: map_kv_dicts(v, fn) for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        return type(tree)(map_kv_dicts(v, fn) for v in tree)
    return tree


def _probe_axes(lm, max_len: int):
    """Structurally discover each cache leaf's batch and sequence axis.

    Returns (treedef, template_leaves, batch_axes, seq_axes); a leaf
    with no sequence axis (SSM recurrent state) gets seq axis None.
    Shape-only (`eval_shape`) — nothing is allocated.
    """
    s1 = jax.eval_shape(lambda: lm.init_caches(1, max_len, Rep.ID))
    s2 = jax.eval_shape(lambda: lm.init_caches(2, max_len, Rep.ID))
    s3 = jax.eval_shape(lambda: lm.init_caches(1, max_len + 1, Rep.ID))
    treedef = jax.tree.structure(s1)
    batch_axes, seq_axes = [], []
    for a, b, c in zip(
        jax.tree.leaves(s1), jax.tree.leaves(s2), jax.tree.leaves(s3)
    ):
        db = [i for i, (u, v) in enumerate(zip(a.shape, b.shape)) if u != v]
        if len(db) != 1:
            raise ValueError(
                f"cannot identify batch axis: {a.shape} vs {b.shape}"
            )
        ds = [i for i, (u, v) in enumerate(zip(a.shape, c.shape)) if u != v]
        if len(ds) > 1:
            raise ValueError(
                f"cannot identify sequence axis: {a.shape} vs {c.shape}"
            )
        if ds and ds[0] <= db[0]:
            raise ValueError(
                f"unsupported cache layout {a.shape}: sequence axis "
                f"{ds[0]} not after batch axis {db[0]}"
            )
        batch_axes.append(db[0])
        seq_axes.append(ds[0] if ds else None)
    return treedef, jax.tree.leaves(s1), tuple(batch_axes), tuple(seq_axes)


def _arena_place(arena, kv_shard: bool):
    """Compute the arena's leaf shardings and device_put its caches.

    Returns the leaf-aligned NamedSharding list (None without a mesh).
    With `kv_shard` each KV leaf splits along its kv-head axis on the
    mesh "model" axis (sharding/rules.arena_leaf_spec — GQA-aware:
    indivisible head counts degrade to replication); page tables, slot
    metadata, and sequence-axis-free leaves (SSM recurrent state)
    replicate.  Without `kv_shard` everything replicates, which gives
    the mesh-but-unsharded ablation point.
    """
    if arena.mesh is None:
        return None
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.sharding.rules import arena_shardings

    leaves = jax.tree.leaves(arena.caches)
    if kv_shard:
        shs = arena_shardings(
            arena.mesh,
            [x.shape for x in leaves],
            arena._batch_axes,
            arena._seq_axes,
        )
    else:
        shs = [NamedSharding(arena.mesh, P()) for _ in leaves]
    arena.caches = jax.device_put(
        arena.caches, jax.tree.unflatten(arena._treedef, shs)
    )
    return shs


def _out_shardings(shardings) -> dict:
    """jit kwargs pinning `shardings` on the outputs (empty off-mesh)."""
    return {} if shardings is None else {"out_shardings": shardings}


class _PrefixNode:
    """One registered full page of prefix content (trie node).

    Keyed in its parent's ``children`` dict by the raw bytes of the
    page's int32 tokens: the CONTENT is the key, chained from the
    root, so reaching a node at depth d certifies the whole token
    prefix [0, (d+1)*page_size) byte-for-byte — no hash collisions to
    reason about.  That chain is exactly the KV dependency structure:
    the KV image at position p is a function of tokens[0..p], so a
    page is reusable iff every token up to its end matches.
    """

    __slots__ = ("parent", "key", "page", "children")

    def __init__(self, parent, key: bytes, page: int):
        self.parent = parent
        self.key = key
        self.page = page
        self.children: dict = {}


@runtime_checkable
class Arena(Protocol):
    """The engine-facing arena contract (DESIGN.md §Serving).

    `SlotArena` and `PagedArena` have always shared this surface
    informally; the protocol makes it typed and testable, and lets the
    engine (and any scheduling policy's capacity math) depend on the
    contract alone.  The paged-only notions degrade cleanly on the
    contiguous arena: `budget_left` is None (slots are the only gate),
    `pages_needed`/`committed_for` are 0, `release_pages` frees
    nothing.

    Lifecycle: `can_admit` -> `alloc` (lease + commit worst case) ->
    `touch`/`touch_range` (materialize on demand) -> `release` (or
    `release_pages` + `release`, the preemption reclaim half).
    Dispatch plumbing: `decode_view`/`absorb` bracket every dispatch —
    the fused decode of the non-chunked oracle modes and the unified
    prefill+decode step of the chunked default alike — plus
    `write_slot` for the one-shot whole-prompt prefill scatter.
    """

    n_slots: int
    max_len: int

    # -- capacity / admission --
    @property
    def n_free(self) -> int: ...

    @property
    def n_leased(self) -> int: ...

    @property
    def budget_left(self) -> Optional[int]:
        """Uncommitted page budget (None: no page dimension)."""
        ...

    def can_admit(
        self, prompt_len: int, total_len: int, tokens=None
    ) -> bool: ...

    def check_request(self, prompt_len: int, total_len: int): ...

    def pages_needed(self, total_len: int) -> int:
        """Worst-case page commitment for a request (0: unpaged)."""
        ...

    def admit_cost(self, total_len: int, tokens=None) -> int:
        """Pages a request must bring of its own: `pages_needed` minus
        whatever a registered prefix of `tokens` already holds (shared
        pages are charged once — DESIGN.md §Prefix-caching ¶Suffix-only
        admission; 0: unpaged)."""
        ...

    def committed_for(self, slot: int) -> int:
        """Pages committed to `slot`'s lease (0: unpaged) — what a
        preemption of this slot would hand back to the budget."""
        ...

    # -- lifecycle --
    def alloc(
        self,
        req_id: int,
        prompt_len: int,
        total_len: Optional[int] = None,
        written: Optional[int] = None,
        tokens=None,
    ) -> int: ...

    def touch(self, slot: int, pos: int): ...

    def touch_range(self, slot: int, start: int, end: int): ...

    def register_prefix(self, slot: int, tokens, upto: int):
        """Publish `slot`'s immutable full pages over positions
        [0, upto) to the prefix cache (no-op when disabled/unpaged)."""
        ...

    def flush_cache(self) -> int:
        """Evict every warm (unreferenced, registered) page now;
        returns how many were evicted (0: unpaged/disabled)."""
        ...

    def release(self, slot: int): ...

    def release_pages(self, slot: int) -> List[int]:
        """Reclaim the slot's physical pages without ending the lease
        (the preemption primitive; [] for the unpaged arena)."""
        ...

    def advance(self, slot: int, n: int = 1): ...

    # -- dispatch plumbing --
    def write_slot(self, slot: int, single_caches): ...

    def decode_view(self): ...

    def absorb(self, new_caches): ...

    def cache_shardings(self): ...

    def decode_shardings(self): ...

    # -- observability --
    def reject_reason(self, prompt_len: int, total_len: int) -> str: ...

    def span_pages(self, slot: int, start: int, end: int) -> list: ...

    def gauges(self) -> dict: ...

    def stats(self) -> dict: ...

    def reset_peaks(self): ...


def make_arena(lm, cfg: "ServingConfig") -> "Arena":
    """Build the arena a ServingConfig describes (the one construction
    site for both strategies; exported from serving/__init__)."""
    if cfg.paged:
        n_pages = cfg.n_pages
        if n_pages is None:
            # default: the same arena positions a contiguous SlotArena
            # of this geometry would reserve
            n_pages = -(-(cfg.n_slots * cfg.max_len) // cfg.page_size)
        return PagedArena(
            lm,
            n_slots=cfg.n_slots,
            max_len=cfg.max_len,
            page_size=cfg.page_size,
            n_pages=n_pages,
            mesh=cfg.mesh,
            kv_shard=cfg.kv_shard,
            prefix_cache=cfg.prefix_cache,
            keep_pages=cfg.cache_keep_pages,
            kv_bits=cfg.kv_bits,
        )
    return SlotArena(
        lm, cfg.n_slots, cfg.max_len, mesh=cfg.mesh, kv_shard=cfg.kv_shard
    )


class SlotArena:
    """Owns the cache arena + slot lifecycle (free -> leased -> free).

    `mesh` + `kv_shard` (DESIGN.md §Serving ¶Multi-device): with a mesh
    the arena is placed with explicit NamedShardings — KV leaves split
    along kv heads on the "model" axis when `kv_shard`, everything
    replicated otherwise — and every internal scatter/gather jit pins
    the same shardings on its outputs, so the arena never silently
    migrates layout between engine steps.
    """

    def __init__(self, lm, n_slots: int, max_len: int, *,
                 mesh=None, kv_shard: bool = False):
        if max_len > lm.max_seq:
            raise ValueError(
                f"max_len {max_len} exceeds model max_seq {lm.max_seq}"
            )
        self.n_slots = n_slots
        self.max_len = max_len
        self.caches = lm.init_caches(n_slots, max_len, Rep.ID)

        (
            self._treedef,
            _,
            self._batch_axes,
            self._seq_axes,
        ) = _probe_axes(lm, max_len)
        self.mesh = mesh
        self._shardings = _arena_place(self, kv_shard)

        def _scatter(arena, single, slot):
            la = jax.tree.leaves(arena)
            ls = jax.tree.leaves(single)
            out = [
                jax.lax.dynamic_update_slice_in_dim(x, y, slot, axis=ax)
                for x, y, ax in zip(la, ls, self._batch_axes)
            ]
            return jax.tree.unflatten(self._treedef, out)

        self._scatter = jax.jit(
            _scatter, **_out_shardings(self.cache_shardings())
        )

        # slot bookkeeping (host-side)
        self._free = list(range(n_slots - 1, -1, -1))  # pop() -> slot 0
        self.lengths = np.zeros(n_slots, np.int32)  # written positions
        self.owner: List[Optional[int]] = [None] * n_slots

    # -- lifecycle ------------------------------------------------------
    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_leased(self) -> int:
        return self.n_slots - len(self._free)

    @property
    def budget_left(self) -> Optional[int]:
        """No page dimension: slots are the only admission gate."""
        return None

    def can_admit(
        self, prompt_len: int, total_len: int, tokens=None
    ) -> bool:
        """A free slot always holds a worst-case request (`tokens` is
        the prefix-cache hook; nothing to share here)."""
        return bool(self._free)

    def check_request(self, prompt_len: int, total_len: int):
        """Slot capacity is length-gated by the scheduler; no-op."""

    def pages_needed(self, total_len: int) -> int:
        """Contiguous rows commit no pages."""
        return 0

    def admit_cost(self, total_len: int, tokens=None) -> int:
        """Contiguous rows commit no pages (and share none)."""
        return 0

    def committed_for(self, slot: int) -> int:
        """Contiguous rows commit no pages."""
        return 0

    def register_prefix(self, slot: int, tokens, upto: int):
        """No page granularity, nothing to share; no-op."""

    def flush_cache(self) -> int:
        """No prefix cache on the contiguous arena."""
        return 0

    def alloc(
        self,
        req_id: int,
        prompt_len: int,
        total_len: Optional[int] = None,
        written: Optional[int] = None,
        tokens=None,
    ) -> int:
        """Lease a free slot to `req_id`; returns the slot index.

        `written` is how many prompt positions are materialized at
        admission: the whole prompt for the one-shot prefill path
        (default), 0 for chunked prefill, where the engine advances the
        slot chunk by chunk (partial-prefill state)."""
        if not self._free:
            raise RuntimeError("no free slots")
        slot = self._free.pop()
        self.owner[slot] = req_id
        self.lengths[slot] = prompt_len if written is None else written
        return slot

    def release(self, slot: int):
        """Recycle a slot.  Contents stay stale in the arena — masked
        until the next tenant's prefill/decode overwrites them."""
        if self.owner[slot] is None:
            raise RuntimeError(f"slot {slot} is not leased")
        self.owner[slot] = None
        self.lengths[slot] = 0
        self._free.append(slot)

    def release_pages(self, slot: int) -> List[int]:
        """Nothing page-granular to reclaim: a preempted slot's rows
        are recycled by release() alone (stale contents stay masked)."""
        if self.owner[slot] is None:
            raise RuntimeError(f"slot {slot} is not leased")
        return []

    # -- shardings ------------------------------------------------------
    def cache_shardings(self):
        """NamedSharding pytree matching `self.caches` (None off-mesh)."""
        if self._shardings is None:
            return None
        return jax.tree.unflatten(self._treedef, self._shardings)

    def decode_shardings(self):
        """Shardings of decode_view() — the arena tree itself (the
        unified dispatch reuses it: same tree, same specs)."""
        return self.cache_shardings()

    # -- cache plumbing -------------------------------------------------
    def write_slot(self, slot: int, single_caches):
        """Scatter a B=1 cache pytree (a finished prefill) into the
        arena at `slot`'s batch row.  One jit'd scatter, slot traced —
        no per-slot recompilation."""
        self.caches = self._scatter(
            self.caches, single_caches, jnp.int32(slot)
        )

    def touch(self, slot: int, pos: int):
        """Contiguous rows need no on-demand growth; no-op."""

    def touch_range(self, slot: int, start: int, end: int):
        """Contiguous rows need no on-demand growth; no-op."""

    def decode_view(self):
        """The cache pytree handed to the jit'd decode step."""
        return self.caches

    def absorb(self, new_caches):
        """Store the cache pytree returned by the decode step."""
        self.caches = new_caches

    def advance(self, slot: int, n: int = 1):
        self.lengths[slot] += n

    def reset_peaks(self):
        """No high-water marks to reset for the contiguous arena."""

    # -- telemetry ------------------------------------------------------
    def reject_reason(self, prompt_len: int, total_len: int) -> str:
        """Why can_admit said no — a free slot is the only gate here."""
        return "no_slot"

    def span_pages(self, slot: int, start: int, end: int) -> list:
        """Physical pages backing positions [start, end): contiguous
        rows have no pages (the telemetry `prefill_chunk` event's page
        context is a paged-arena concept)."""
        return []

    def gauges(self) -> dict:
        """Instantaneous occupancy sampled into each telemetry step
        record (DESIGN.md §Observability ¶Span model) — host counters
        only, so sampling never touches the device."""
        return {
            "n_leased": self.n_leased,
            "n_free": self.n_free,
            "occupancy": self.n_leased / self.n_slots,
        }

    def stats(self) -> dict:
        return {
            "arena": "slot",
            "arena_positions": self.n_slots * self.max_len,
        }


class PagedArena:
    """Paged KV arena: page pool + per-slot page table + slot rows.

    Admission commits a request's own worst-case page budget (so an
    on-demand allocation mid-decode can never fail — preemption-free
    by construction), but physical pages are allocated lazily as
    decode advances and recycled wholesale on completion.  Short
    requests therefore stop reserving `max_len` worst-case rows, and
    the same arena bytes admit more concurrent requests.

    ``prefix_cache=True`` adds refcounted page sharing (DESIGN.md
    §Prefix-caching): a content-keyed trie maps immutable full-page
    token prefixes to physical pages, admission installs the longest
    registered match into the new slot's table row and charges only
    the unshared suffix, `touch` copy-on-writes before the first
    divergent write, and pages whose last reference drops stay WARM
    (registered, refcount 0) under the ``keep_pages`` lazy-eviction
    budget.  The running soundness invariant is
    ``committed_pages + pinned_cache_pages <= n_pages`` — every future
    on-demand pop is covered by free + warm pages, so decode still
    never deadlocks on an empty pool.
    """

    def __init__(
        self,
        lm,
        n_slots: int,
        max_len: int,
        page_size: int = 128,
        n_pages: int = 64,
        *,
        mesh=None,
        kv_shard: bool = False,
        prefix_cache: bool = False,
        keep_pages: int = 0,
        kv_bits: int = 8,
    ):
        if max_len > lm.max_seq:
            raise ValueError(
                f"max_len {max_len} exceeds model max_seq {lm.max_seq}"
            )
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        if n_pages < 1:
            raise ValueError(f"n_pages must be >= 1, got {n_pages}")
        if kv_bits not in (8, 4):
            raise ValueError(f"kv_bits must be 8 or 4, got {kv_bits}")
        self.n_slots = n_slots
        self.max_len = max_len
        self.page_size = page_size
        self.n_pages = n_pages
        self.kv_bits = kv_bits
        self.pages_per_slot = -(-max_len // page_size)

        (
            self._treedef,
            template,
            self._batch_axes,
            self._seq_axes,
        ) = _probe_axes(lm, max_len)

        # Pool: paged leaves swap (B, max_len) for (n_pages + 1,
        # page_size) and move the page_size axis last — position-minor
        # pages (..., K, hd, page_size), the layout the TPU kernel
        # copies whole (kernels/paged_attention.py ¶Memory scope);
        # per-slot leaves (no sequence axis) keep B=n_slots.
        # kv_bits=4 (DESIGN.md §Serving ¶Sub-8-bit KV) additionally
        # halves each KV leaf's head_dim — two int4 nibbles per int8
        # cell, packed along hd so every page cell stays
        # position-complete and the page/table math is untouched.
        leaves = []
        for leaf, b_ax, s_ax in zip(
            template, self._batch_axes, self._seq_axes
        ):
            shape = list(leaf.shape)
            if s_ax is None:
                shape[b_ax] = n_slots
            else:
                shape[b_ax] = n_pages + 1  # + the PAGE_NULL trash page
                shape[s_ax] = page_size
                if kv_bits == 4:
                    last = len(shape) - 1
                    if s_ax == last or b_ax == last:
                        raise ValueError(
                            "kv_bits=4 needs a trailing head_dim axis "
                            f"to pack, got KV leaf shape {leaf.shape}"
                        )
                    if shape[last] % 2:
                        raise ValueError(
                            "kv_bits=4 needs an even head_dim, got "
                            f"{shape[last]}"
                        )
                    shape[last] //= 2
                shape.append(shape.pop(s_ax))
            leaves.append(jnp.zeros(shape, leaf.dtype))
        self.caches = jax.tree.unflatten(self._treedef, leaves)
        # pool leaves swap (B, T) for (pages, page_size) but keep the
        # kv-head axis in place, so the same structural rule shards
        # them (sharding/rules.arena_leaf_spec on the pool shapes)
        self.mesh = mesh
        self._shardings = _arena_place(self, kv_shard)

        # Every paged leaf must live inside a {'k','v'} dict so the
        # decode step finds a page table next to it.
        n_paged = sum(s is not None for s in self._seq_axes)
        n_kv = [0]

        def _count(d):
            n_kv[0] += 1
            return d

        map_kv_dicts(self.caches, _count)
        if n_paged != 2 * n_kv[0]:
            raise ValueError(
                f"unsupported cache layout: {n_paged} paged leaves but "
                f"{n_kv[0]} attention KV dicts"
            )

        def _write(arena_leaves, single_leaves, table_row, slot):
            """Scatter a B=1 prefill result into pages / slot rows."""
            t_pad = self.pages_per_slot * self.page_size
            out = []
            for x, y, b_ax, s_ax in zip(
                arena_leaves,
                single_leaves,
                self._batch_axes,
                self._seq_axes,
            ):
                if s_ax is None:
                    out.append(
                        jax.lax.dynamic_update_slice_in_dim(
                            x, y, slot, axis=b_ax
                        )
                    )
                    continue
                z = jnp.squeeze(y, axis=b_ax)
                sa = s_ax - 1  # sequence axis after dropping batch
                if z.shape[sa] < t_pad:
                    widths = [(0, 0)] * z.ndim
                    widths[sa] = (0, t_pad - z.shape[sa])
                    z = jnp.pad(z, widths)
                shp = z.shape
                z = z.reshape(
                    shp[:sa]
                    + (self.pages_per_slot, self.page_size)
                    + shp[sa + 1 :]
                )
                # position-minor pages, then the page axis at b_ax
                z = jnp.moveaxis(jnp.moveaxis(z, sa + 1, -1), sa, b_ax)
                idx = (slice(None),) * b_ax + (table_row,)
                # unallocated logical blocks land on the trash page
                out.append(x.at[idx].set(z))
            return out

        self._write = jax.jit(_write, **_out_shardings(self._shardings))

        # page-table lead dims: one kv dict per attention cache site,
        # each stacked under the same leading axes as its 'k' leaf
        # (n_layers, [pair, ...]); recorded in map_kv_dicts order so
        # decode_view() can zip them back deterministically.
        zipped = jax.tree.map(
            lambda a, b: (a, b),
            jax.eval_shape(lambda: lm.init_caches(1, max_len, Rep.ID)),
            jax.eval_shape(lambda: lm.init_caches(2, max_len, Rep.ID)),
        )
        self._kv_batch_axes: List[int] = []

        def _grab(d):
            a, b = d["k"]
            diff = [
                i for i, (u, v) in enumerate(zip(a.shape, b.shape)) if u != v
            ]
            self._kv_batch_axes.append(diff[0])
            return d

        map_kv_dicts(zipped, _grab)

        # page + slot bookkeeping (host-side); pop() -> lowest first
        self._free_slots = list(range(n_slots - 1, -1, -1))
        self._free_pages = list(range(n_pages, 0, -1))
        self.page_table = np.full(
            (n_slots, self.pages_per_slot), PAGE_NULL, np.int32
        )
        self.lengths = np.zeros(n_slots, np.int32)
        self.owner: List[Optional[int]] = [None] * n_slots
        self._commit = np.zeros(n_slots, np.int32)
        self.committed_pages = 0
        self.max_pages_in_use = 0
        self.max_committed = 0

        # prefix cache (DESIGN.md §Prefix-caching) — all host-side:
        # refcounts count table-row references per physical page;
        # the trie maps full-page token content to pages; _warm holds
        # registered refcount-0 pages in LRU (insertion) order.  The
        # refcount array is maintained even with the cache off (it is
        # cheap and lets the leak property test cover both modes).
        self.prefix_cache = bool(prefix_cache)
        self.keep_pages = int(keep_pages)
        self.refcount = np.zeros(n_pages + 1, np.int32)
        self._trie_root = _PrefixNode(None, b"", PAGE_NULL)
        self._page_node: dict = {}  # physical page -> _PrefixNode
        self._warm: dict = {}  # page -> None, LRU by insertion
        self._slot_node: List[_PrefixNode] = [self._trie_root] * n_slots
        self._slot_registered = np.zeros(n_slots, np.int32)
        self.shared_at_admit = np.zeros(n_slots, np.int32)
        self.on_cow = None  # engine hook: fn(slot, old_page, new_page)
        self.prefix_hits = 0  # admissions that matched >= 1 page
        self.prefix_misses = 0  # cache-eligible admissions, no match
        self.prefix_hit_pages = 0  # pages served without recompute
        self.cow_splits = 0
        self.warm_evictions = 0

        # CoW split: pool[dst] <- pool[src] on every paged leaf.
        # src/dst traced (compiles once); shardings pinned like every
        # other arena jit, and pages are kv-head-complete per shard,
        # so the copy is shard-local on a mesh.
        def _copy_page(arena_leaves, src, dst):
            out = []
            for x, b_ax, s_ax in zip(
                arena_leaves, self._batch_axes, self._seq_axes
            ):
                if s_ax is None:
                    out.append(x)
                    continue
                row = jax.lax.dynamic_index_in_dim(
                    x, src, axis=b_ax, keepdims=False
                )
                out.append(x.at[(slice(None),) * b_ax + (dst,)].set(row))
            return out

        self._copy_page = jax.jit(
            _copy_page, **_out_shardings(self._shardings)
        )

    # -- page accounting ------------------------------------------------
    def _pages_for(self, total_len: int) -> int:
        """Worst-case pages for a request writing [0, total_len - 1):
        prefill fills [0, P) and the last decode writes P + G - 2."""
        return -(-max(total_len - 1, 1) // self.page_size)

    @property
    def n_free(self) -> int:
        return len(self._free_slots)

    @property
    def n_leased(self) -> int:
        return self.n_slots - len(self._free_slots)

    @property
    def pages_in_use(self) -> int:
        return self.n_pages - len(self._free_pages)

    @property
    def free_pages(self) -> int:
        return len(self._free_pages)

    @property
    def cache_pages(self) -> int:
        """Pages the prefix cache owns (registered in the trie) —
        charged to the cache ledger, not to any slot's commit."""
        return len(self._page_node)

    @property
    def warm_pages(self) -> int:
        """Registered pages with no referencing slot, kept allocated
        under the keep budget; evictable on demand."""
        return len(self._warm)

    @property
    def pinned_cache_pages(self) -> int:
        """Cache-owned pages admission cannot reclaim: registered
        pages some slot still references.  Warm pages are NOT pinned —
        lazy eviction hands them back the moment a pop needs one."""
        return len(self._page_node) - len(self._warm)

    @property
    def budget_left(self) -> Optional[int]:
        """Uncommitted page budget — what admission (or a policy's
        capacity simulation) may still hand out.  Warm pages count as
        available (evictable); pinned cache pages do not."""
        return (
            self.n_pages - self.committed_pages - self.pinned_cache_pages
        )

    def pages_needed(self, total_len: int) -> int:
        """Worst-case commitment for a request (the protocol name for
        `_pages_for`)."""
        return self._pages_for(total_len)

    def _match_node(self, tokens) -> Tuple[List[int], _PrefixNode]:
        """Walk the trie over `tokens`' full pages: the physical pages
        of the longest registered prefix, plus the deepest node (the
        seed for this slot's own later registrations).  Every
        registered page is resident by construction — in some table
        row or warm — so a match never needs recompute."""
        toks = np.asarray(tokens, np.int32)
        node = self._trie_root
        pages: List[int] = []
        ps = self.page_size
        for blk in range(toks.size // ps):
            child = node.children.get(
                toks[blk * ps : (blk + 1) * ps].tobytes()
            )
            if child is None:
                break
            pages.append(child.page)
            node = child
        return pages, node

    def _discount(self, matched: int, prompt_len: int) -> int:
        """Commit discount for `matched` shared pages of a
        `prompt_len`-token source.  A matched page strictly below the
        re-prefill tail is never written again — a full discount.
        When the match covers the whole prompt the tail still
        recomputes position P-1 (the engine needs its logits), which
        lands INSIDE the last shared page and copy-on-writes into a
        private replacement — so that one page stays in the request's
        own budget."""
        if matched == 0:
            return 0
        if matched * self.page_size < prompt_len:
            return matched
        return matched - 1

    def admit_cost(self, total_len: int, tokens=None) -> int:
        """Pages a request must bring of its OWN: the worst case minus
        the shared-prefix discount (DESIGN.md §Prefix-caching
        ¶Suffix-only admission — a shared page is charged once,
        globally, to the cache ledger)."""
        need = self._pages_for(total_len)
        if tokens is None or not self.prefix_cache:
            return need
        matched, _ = self._match_node(tokens)
        return need - self._discount(len(matched), len(tokens))

    def committed_for(self, slot: int) -> int:
        """Pages committed to `slot`'s lease — returned to the budget
        if a policy preempts it.  Shrinks as the slot's full pages are
        registered (they transfer to the cache ledger)."""
        return int(self._commit[slot])

    def can_admit(
        self, prompt_len: int, total_len: int, tokens=None
    ) -> bool:
        """Admission gate: a free decode row AND uncommitted budget for
        the request's own worst case.  Committing (not materializing)
        the worst case keeps the engine preemption-free: every
        on-demand `touch` is covered, so decode can never deadlock on
        an empty pool.

        With `tokens` and the prefix cache on, the request is charged
        only its unshared suffix — but matched pages that are
        currently WARM stop being evictable the moment they are
        installed, so they re-enter the ledger here (`revive`).  The
        preserved invariant is
        committed_pages + pinned_cache_pages <= n_pages, which is
        exactly "all future pops are covered by free + warm pages"."""
        if not self._free_slots:
            return False
        need = self._pages_for(total_len)
        revive = 0
        if self.prefix_cache and tokens is not None:
            matched, _ = self._match_node(tokens)
            need -= self._discount(len(matched), len(tokens))
            revive = sum(1 for p in matched if p in self._warm)
        return (
            self.committed_pages + self.pinned_cache_pages + revive + need
            <= self.n_pages
        )

    def check_request(self, prompt_len: int, total_len: int):
        need = self._pages_for(total_len)
        if need > self.n_pages:
            raise ValueError(
                f"request needs {need} pages but the arena holds "
                f"{self.n_pages}"
            )

    # -- lifecycle ------------------------------------------------------
    def _pop_page(self) -> int:
        """A free physical page, lazily evicting the LRU warm page
        when the free list is dry — warm pages are cache property
        held only while the budget has no better use for them."""
        if not self._free_pages:
            if not self._warm:
                raise RuntimeError(
                    "page pool exhausted despite commitment accounting"
                )
            self._evict_warm(next(iter(self._warm)))
        return self._free_pages.pop()

    def _evict_warm(self, page: int):
        """Unregister + free one warm page (lazy eviction).  Deeper
        trie nodes under it become unreachable from the root — their
        prefix content is gone, so they can no longer match — and age
        out of the warm list on their own."""
        del self._warm[page]
        node = self._page_node.pop(page)
        if node.parent is not None:
            node.parent.children.pop(node.key, None)
        self._free_pages.append(page)
        self.warm_evictions += 1

    def _retire(self, page: int):
        """A registered page's last reference dropped: keep it warm
        under the keep budget (LRU by retirement order), else evict
        immediately."""
        self._warm[page] = None
        while len(self._warm) > self.keep_pages:
            self._evict_warm(next(iter(self._warm)))

    def alloc(
        self,
        req_id: int,
        prompt_len: int,
        total_len: Optional[int] = None,
        written: Optional[int] = None,
        tokens=None,
    ) -> int:
        """Lease a slot + commit the page budget; allocate pages for the
        positions materialized at admission — the whole prompt for the
        one-shot prefill path (`written` None), none for chunked
        prefill (`written` 0), whose pages arrive chunk by chunk via
        touch_range (partial-prefill state).

        `tokens` (prefix cache, chunked path only): the request's
        source tokens.  The longest registered full-page prefix is
        installed into the slot's table row — refcounted, charged to
        the cache ledger, not this commit — and `lengths[slot]`
        reports how many leading positions admission made valid; the
        engine starts its chunk cursor there.  The skip is capped at
        prompt_len - 1 so the tail always recomputes at least the
        last prompt position (its logits seed decode)."""
        total_len = prompt_len if total_len is None else total_len
        use = (
            tokens
            if self.prefix_cache and tokens is not None and written == 0
            else None
        )
        if not self.can_admit(prompt_len, total_len, tokens=use):
            raise RuntimeError("out of slots or page budget")
        slot = self._free_slots.pop()
        self.owner[slot] = req_id
        if use is not None:
            shared, node = self._match_node(use)
            need = self._pages_for(total_len) - self._discount(
                len(shared), len(use)
            )
        else:
            shared, node = [], self._trie_root
            need = self._pages_for(total_len)
        self._commit[slot] = need
        self.committed_pages += need
        self.max_committed = max(self.max_committed, self.committed_pages)
        # install the shared prefix: cache-owned pages enter the table
        # row refcounted; warm ones are revived (pinned again)
        for blk, page in enumerate(shared):
            self.page_table[slot, blk] = page
            self.refcount[page] += 1
            self._warm.pop(page, None)
        if self.prefix_cache:
            self._slot_node[slot] = node
            self._slot_registered[slot] = len(shared)
            self.shared_at_admit[slot] = len(shared)
            if use is not None:
                if shared:
                    self.prefix_hits += 1
                    self.prefix_hit_pages += len(shared)
                else:
                    self.prefix_misses += 1
        if shared:
            materialized = min(
                len(shared) * self.page_size, int(prompt_len) - 1
            )
        else:
            materialized = prompt_len if written is None else written
        self.lengths[slot] = materialized
        for blk in range(
            len(shared), -(-materialized // self.page_size)
        ):
            page = self._pop_page()
            self.page_table[slot, blk] = page
            self.refcount[page] = 1
        self.max_pages_in_use = max(self.max_pages_in_use, self.pages_in_use)
        return slot

    def touch(self, slot: int, pos: int):
        """On-demand page allocation before the decode that writes at
        `pos`.  Covered by the admission-time commitment, so the free
        list (plus lazily evictable warm pages) cannot be empty here.

        Copy-on-write (DESIGN.md §Prefix-caching ¶Copy-on-write): when
        the covering page is shared (refcount > 1) or registered in
        the trie, the slot must not write into it — pop a private
        page, device-copy the contents, swap the table entry.  The
        engine touches before building any dispatch view, so the
        jit'd write paths (layers/attention paged writes) only ever
        see exclusively-owned target pages and need no change."""
        blk = pos // self.page_size
        page = int(self.page_table[slot, blk])
        if page != PAGE_NULL:
            if self.prefix_cache and (
                self.refcount[page] > 1 or page in self._page_node
            ):
                self._cow(slot, blk, page)
            return
        new = self._pop_page()
        self.page_table[slot, blk] = new
        self.refcount[new] = 1
        self.max_pages_in_use = max(self.max_pages_in_use, self.pages_in_use)

    def _cow(self, slot: int, blk: int, old: int):
        """Copy-on-write split of `slot`'s logical block `blk`.  The
        pop is covered by the slot's commit: the only CoW site under
        engine discipline is the re-prefill tail rewriting the last
        position of a page-aligned exact match, whose replacement page
        `_discount` deliberately left in the request's budget."""
        new = self._pop_page()
        leaves = self._copy_page(
            jax.tree.leaves(self.caches), jnp.int32(old), jnp.int32(new)
        )
        self.caches = jax.tree.unflatten(self._treedef, leaves)
        self.page_table[slot, blk] = new
        self.refcount[new] = 1
        self.refcount[old] -= 1
        if self.refcount[old] == 0:
            if old in self._page_node:
                self._retire(old)
            else:  # unshared + unregistered: plain free (defensive)
                self._free_pages.append(old)
        self.cow_splits += 1
        self.max_pages_in_use = max(self.max_pages_in_use, self.pages_in_use)
        if self.on_cow is not None:
            self.on_cow(slot, old, new)

    def touch_range(self, slot: int, start: int, end: int):
        """Allocate every page covering positions [start, end) before a
        chunked-prefill dispatch writes there (chunk writes past `end`
        — the padded tail of a final partial chunk — deliberately land
        on the trash page, so only real positions need pages)."""
        if end <= start:
            return
        for blk in range(
            start // self.page_size, (end - 1) // self.page_size + 1
        ):
            self.touch(slot, blk * self.page_size)

    def release_pages(self, slot: int) -> List[int]:
        """Return ALL of `slot`'s physical pages to the free pool and
        point its table row back at PAGE_NULL, WITHOUT ending the lease
        — the reclaim half of preemption (DESIGN.md §Scheduling).  Page
        contents stay stale; the evicted request's re-prefill (or a
        future tenant) overwrites every block before any of its
        positions become visible.  Returns the freed page ids."""
        if self.owner[slot] is None:
            raise RuntimeError(f"slot {slot} is not leased")
        freed = []
        for blk in range(self.pages_per_slot):
            page = int(self.page_table[slot, blk])
            if page == PAGE_NULL:
                continue
            self.page_table[slot, blk] = PAGE_NULL
            self.refcount[page] -= 1
            if self.refcount[page] > 0:
                continue  # other table rows still share this page
            if page in self._page_node:
                # registered: retire warm under the keep budget
                # (DESIGN.md §Prefix-caching ¶Warm pages) instead of
                # freeing — a matching re-admission revives it
                self._retire(page)
                continue
            self._free_pages.append(page)
            freed.append(page)
        self.lengths[slot] = 0
        if self.prefix_cache:
            self._slot_node[slot] = self._trie_root
            self._slot_registered[slot] = 0
            self.shared_at_admit[slot] = 0
        return freed

    def release(self, slot: int):
        """Recycle the slot and ALL its pages (release_pages + end the
        lease and uncommit the budget)."""
        self.release_pages(slot)
        self.owner[slot] = None
        self.committed_pages -= int(self._commit[slot])
        self._commit[slot] = 0
        self._free_slots.append(slot)

    def register_prefix(self, slot: int, tokens, upto: int):
        """Publish `slot`'s immutable FULL pages covering positions
        [0, upto) to the prefix cache, transferring each newly
        registered page from the slot's commit to the cache ledger
        (charged once globally from here on — the slot's own release
        or preemption no longer un-pays it while sharers remain).

        Exactness: integer decode is deterministic, so the KV image
        of the page holding positions [b*ps, (b+1)*ps) is a pure
        function of tokens[0 : (b+1)*ps]; chaining page-content keys
        from the root certifies exactly the bytes a matching request
        would recompute (DESIGN.md §Prefix-caching ¶Exactness).

        Idempotent per slot via a block cursor (re-registration of
        the same blocks is free); when another slot already
        registered identical content, the first registrant's pages
        win and this slot's stay private.  No-op with the cache off,
        and only ever called by the engine's chunked path — full
        pages there are final, never rewritten."""
        if not self.prefix_cache or self.owner[slot] is None:
            return
        nblk = min(int(upto) // self.page_size, self.pages_per_slot)
        cur = int(self._slot_registered[slot])
        if nblk <= cur:
            return
        toks = np.asarray(tokens, np.int32)
        ps = self.page_size
        node = self._slot_node[slot]
        for blk in range(cur, nblk):
            key = toks[blk * ps : (blk + 1) * ps].tobytes()
            child = node.children.get(key)
            if child is None:
                page = int(self.page_table[slot, blk])
                child = _PrefixNode(node, key, page)
                node.children[key] = child
                self._page_node[page] = child
                # ownership transfer: slot-paid -> cache-paid
                self._commit[slot] -= 1
                self.committed_pages -= 1
            node = child
        self._slot_node[slot] = node
        self._slot_registered[slot] = nblk

    def flush_cache(self) -> int:
        """Evict every warm page now (drop the retained-but-unused
        cache state; registered pages still referenced by a slot are
        untouched and will retire normally).  Returns the eviction
        count — after a full drain + flush the pool is back to
        pristine: zero pages in use, every refcount zero."""
        n = 0
        while self._warm:
            self._evict_warm(next(iter(self._warm)))
            n += 1
        return n

    # -- shardings ------------------------------------------------------
    def cache_shardings(self):
        """NamedSharding pytree matching `self.caches` (None off-mesh)."""
        if self._shardings is None:
            return None
        return jax.tree.unflatten(self._treedef, self._shardings)

    def decode_shardings(self):
        """Shardings of decode_view(): pool shardings with the injected
        page tables REPLICATED — every shard needs the full table to
        walk its own heads' pages (DESIGN.md §Serving ¶Multi-device:
        only the kv-head axis splits; pages are head-complete)."""
        tree = self.cache_shardings()
        if tree is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec as P

        repl = NamedSharding(self.mesh, P())
        return map_kv_dicts(tree, lambda d: {**d, "table": repl})

    # -- cache plumbing -------------------------------------------------
    def write_slot(self, slot: int, single_caches):
        """Scatter a B=1 cache pytree (a finished prefill) through the
        slot's page-table row.  One jit'd scatter, table traced."""
        la = jax.tree.leaves(self.caches)
        ls = jax.tree.leaves(single_caches)
        out = self._write(
            la, ls, jnp.asarray(self.page_table[slot]), jnp.int32(slot)
        )
        self.caches = jax.tree.unflatten(self._treedef, out)

    def decode_view(self):
        """Attach the current page table inside every attention cache
        dict (broadcast over its stacked leading axes) — the decode
        step's cache pytree keeps one structure, so paging costs no
        extra compilation.

        This IS the fused paged-attention kernel's layout contract
        (kernels/paged_attention.py): position-minor int8 pools
        (n_pages + 1, K, hd, page_size) with physical page 0 reserved
        as the PAGE_NULL trash page, an int32 (n_slots,
        pages_per_slot) table whose stale/unallocated entries point at
        PAGE_NULL, and the engine's int32 per-slot position vector
        alongside.  The kernel reads K/V straight through this view —
        no dense logical gather on the decode hot path."""
        tab = jnp.asarray(self.page_table)
        axes = iter(self._kv_batch_axes)

        def _attach(d):
            lead = d["k"].shape[: next(axes)]
            return {**d, "table": jnp.broadcast_to(tab, lead + tab.shape)}

        return map_kv_dicts(self.caches, _attach)

    def absorb(self, new_caches):
        """Strip the page tables back out of the decode result."""
        self.caches = map_kv_dicts(
            new_caches,
            lambda d: {k: v for k, v in d.items() if k != "table"},
        )

    def advance(self, slot: int, n: int = 1):
        self.lengths[slot] += n

    def reset_peaks(self):
        """Restart the page high-water marks from the current state
        (engine.reset_stats: a warmup window's peaks must not leak
        into the measured window's report)."""
        self.max_pages_in_use = self.pages_in_use
        self.max_committed = self.committed_pages
        self.prefix_hits = 0
        self.prefix_misses = 0
        self.prefix_hit_pages = 0
        self.cow_splits = 0
        self.warm_evictions = 0

    # -- telemetry ------------------------------------------------------
    def reject_reason(self, prompt_len: int, total_len: int) -> str:
        """Why can_admit said no: decode rows exhausted, or the page
        budget (the request's own worst case would overcommit the
        pool) — the two distinct backpressure causes a scheduler on
        top of this arena needs to tell apart."""
        if not self._free_slots:
            return "no_slot"
        return "no_pages"

    def span_pages(self, slot: int, start: int, end: int) -> list:
        """Physical pages backing positions [start, end) of `slot`
        (the telemetry `prefill_chunk` event's page context).  Call
        after touch_range: every covered block is then materialized,
        so no PAGE_NULL appears for a real position."""
        if end <= start:
            return []
        ps = self.page_size
        return [
            int(self.page_table[slot, blk])
            for blk in range(start // ps, (end - 1) // ps + 1)
        ]

    def gauges(self) -> dict:
        """Instantaneous occupancy + page pressure sampled into each
        telemetry step record (DESIGN.md §Observability ¶Span model)."""
        out = {
            "n_leased": self.n_leased,
            "n_free": self.n_free,
            "occupancy": self.n_leased / self.n_slots,
            "pages_in_use": self.pages_in_use,
            "free_pages": self.free_pages,
            "committed_pages": self.committed_pages,
            "max_pages_in_use": self.max_pages_in_use,
        }
        if self.prefix_cache:
            out["cache_pages"] = self.cache_pages
            out["warm_pages"] = self.warm_pages
        return out

    def stats(self) -> dict:
        out = {
            "arena": "paged",
            "arena_positions": self.n_pages * self.page_size,
            "page_size": self.page_size,
            "n_pages": self.n_pages,
            "kv_bits": self.kv_bits,
            "pages_in_use": self.pages_in_use,
            "committed_pages": self.committed_pages,
            "max_pages_in_use": self.max_pages_in_use,
            "max_committed_pages": self.max_committed,
        }
        if self.prefix_cache:
            out.update(
                {
                    "prefix_cache": True,
                    "cache_keep_pages": self.keep_pages,
                    "cache_pages": self.cache_pages,
                    "warm_pages": self.warm_pages,
                    "prefix_hits": self.prefix_hits,
                    "prefix_misses": self.prefix_misses,
                    "prefix_hit_pages": self.prefix_hit_pages,
                    "cow_splits": self.cow_splits,
                    "warm_evictions": self.warm_evictions,
                }
            )
        return out
