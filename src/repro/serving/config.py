"""`ServingConfig`: one validated construction record for the engine.

`ServingEngine.__init__` grew a keyword per PR (paged geometry, kernel
variant, mesh placement, dispatch pipelining, telemetry...).  This
dataclass collapses the sprawl into a single value the engine — and
`cache.make_arena` — consume, with validation at construction instead
of failure inside the first step.

The `policy` field is the scheduling brain (serving/policy.py,
DESIGN.md §Scheduling): None means `FCFSPolicy()`, today's behavior.
"""

from __future__ import annotations

import dataclasses
from typing import TYPE_CHECKING, Any, Optional

from repro.serving.scheduler import SchedulerConfig

if TYPE_CHECKING:  # pragma: no cover - typing only
    from repro.serving.policy import SchedulingPolicy


@dataclasses.dataclass
class ServingConfig:
    """Everything ServingEngine needs besides the model + tables."""

    # arena geometry
    n_slots: int = 8
    max_len: int = 256
    # paged arena (DESIGN.md §Serving ¶Paged KV)
    paged: bool = False
    # a multiple of 128 on a TPU (kernels/paged_attention.TPU_PAGE_ALIGN)
    page_size: int = 128
    n_pages: Optional[int] = None  # None: SlotArena-equivalent positions
    # paged attention: the fused kernel (False: the write-then-gather
    # oracle); ignored without the paged arena
    paged_kernel: bool = True
    # multi-device placement (DESIGN.md §Serving ¶Multi-device)
    mesh: Any = None
    kv_shard: bool = False
    dispatch_depth: int = 0  # 0 sync, 1 one-step pipeline
    # scheduling: queue shape knobs + the policy that plans each step
    scheduler: Optional[SchedulerConfig] = None
    policy: Optional["SchedulingPolicy"] = None  # None -> FCFSPolicy()
    # observability sink (DESIGN.md §Observability); None -> NULL
    telemetry: Any = None
    # KV storage width (DESIGN.md §Serving ¶Sub-8-bit KV): 8 keeps the
    # bit-exact int8 KV images; 4 packs two int4 nibbles per pool cell
    # (half the arena bytes, per-kv-head requant images, accuracy
    # gated by correlation not bit-exactness).  4 needs the paged
    # arena and the chunked prefill path.
    kv_bits: int = 8
    # prefix caching (DESIGN.md §Prefix-caching): refcounted page
    # sharing across requests + warm pages for preemption resume.
    # Requires the paged arena; sharing engages on the chunked path.
    prefix_cache: bool = False
    # warm-page budget: immutable full pages kept allocated (refcount
    # 0, lazily evicted LRU) after their last holder releases, so a
    # later shared-prefix admission or preemption resume can reuse
    # them without recompute.  0 = evict eagerly on last release.
    cache_keep_pages: int = 0

    def __post_init__(self):
        if self.n_slots < 1:
            raise ValueError(f"n_slots must be >= 1, got {self.n_slots}")
        if self.max_len < 1:
            raise ValueError(f"max_len must be >= 1, got {self.max_len}")
        if self.page_size < 1:
            raise ValueError(
                f"page_size must be >= 1, got {self.page_size}"
            )
        if self.n_pages is not None and self.n_pages < 1:
            raise ValueError(
                f"n_pages must be >= 1, got {self.n_pages}"
            )
        if self.dispatch_depth not in (0, 1):
            raise ValueError(
                "dispatch_depth must be 0 (synchronous) or 1 "
                f"(one in-flight decode), got {self.dispatch_depth}"
            )
        if self.kv_shard and self.mesh is None:
            raise ValueError(
                "kv_shard=True needs a mesh "
                "(launch.mesh.make_serving_mesh)"
            )
        if self.kv_bits not in (8, 4):
            raise ValueError(
                f"kv_bits must be 8 or 4, got {self.kv_bits}"
            )
        if self.kv_bits == 4 and not self.paged:
            raise ValueError(
                "kv_bits=4 needs the paged arena (paged=True): "
                "nibble packing is a page-pool layout"
            )
        if self.prefix_cache and not self.paged:
            raise ValueError(
                "prefix_cache=True needs the paged arena (paged=True): "
                "sharing is page-granular"
            )
        if self.cache_keep_pages < 0:
            raise ValueError(
                "cache_keep_pages must be >= 0, "
                f"got {self.cache_keep_pages}"
            )
        if self.cache_keep_pages and not self.prefix_cache:
            raise ValueError(
                "cache_keep_pages needs prefix_cache=True "
                "(warm pages are prefix-cache state)"
            )
        if self.scheduler is None:
            self.scheduler = SchedulerConfig()
