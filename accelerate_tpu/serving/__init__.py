"""Continuous-batching serving: in-flight admission and chunked prefill —
iteration-level scheduling (Orca; vLLM's slot reuse) kept inside a fixed set
of compiled TPU executables — over a refcounted KV page pool behind per-lane
block tables (:mod:`.paging` — PagedAttention, TPU-native).  See
``docs/usage/serving.md``.
"""

from .engine import ServingEngine
from .errors import AdmissionError, DeadlineExceeded
from .faults import FaultInjected, FaultInjector, FaultPlan
from .paging import NULL_PAGE, MixedKVPool, PageAllocator, PagedKVPool
from .pool import (
    ServeShardings,
    jit_cache_sizes,
    make_copy_page,
    make_paged_decode_window,
    make_paged_prefill_chunk,
    make_paged_verify_window,
    make_promote_install,
    make_spill_extract,
    plan_chunks,
)
from .prefix_cache import PrefixCache, PrefixNode, rolling_hash
from .router import ReplicaRouter
from .scheduler import Request, RequestState, Scheduler
from .spec import propose_ngram_draft
from .transfer import MigrationError, PageMigrator

__all__ = [
    "ServingEngine",
    "AdmissionError",
    "DeadlineExceeded",
    "FaultInjected",
    "FaultInjector",
    "FaultPlan",
    "MigrationError",
    "PageMigrator",
    "ReplicaRouter",
    "ServeShardings",
    "Request",
    "RequestState",
    "Scheduler",
    "PrefixCache",
    "PrefixNode",
    "rolling_hash",
    "NULL_PAGE",
    "MixedKVPool",
    "PageAllocator",
    "PagedKVPool",
    "plan_chunks",
    "make_paged_decode_window",
    "make_paged_verify_window",
    "make_paged_prefill_chunk",
    "make_copy_page",
    "make_spill_extract",
    "make_promote_install",
    "propose_ngram_draft",
    "jit_cache_sizes",
]
