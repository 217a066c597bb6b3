"""Chunk-granular prefix KV cache: a radix tree over chunk-aligned prefixes.

Under a serving queue with shared system/few-shot prefixes, most prefill FLOPs
recompute KV the pool already produced for an earlier request.  SGLang's
RadixAttention and vLLM's automatic prefix caching reuse that KV across
requests; the TPU-native translation caches at **chunk granularity** — the
exact bucket boundaries :func:`~accelerate_tpu.serving.pool.plan_chunks`
already prefills at, each a whole number of KV pages (:mod:`.paging`) — so a
hit is a host-side edit of the lane's block table, no executable runs, and
the compiled-shape budget stays static no matter how requests share.

Structure: a tree whose edges are *full* chunks of token ids.  A node's
identity is the whole token prefix from the root; its key inside the parent is
a rolling hash of that prefix (:func:`rolling_hash`), verified token-exact on
every lookup so a hash collision can never serve wrong KV.  Each node retains
the physical page ids holding the KV that prefill computed for its chunk
*given its full prefix*, with one allocator reference per page — KV at a
position depends on every earlier token through attention, which is why only
exact whole-prefix matches are reusable and why partial (padded) final chunks
are never cached.

Lifecycle: nodes are pinned (``refs``) while any request between admission and
lane installation depends on them; eviction is leaf-only LRU among unpinned
nodes, under a byte ``capacity`` (``ServingEngine(prefix_cache_mb=...)``).
Evicting a leaf may expose its parent as the next candidate — interior nodes
are never dropped from under their children, so every resident node's prefix
chain stays resident.

Tiering: with ``host_capacity_bytes > 0`` and a ``spill``
hook installed, a device-tier eviction *demotes* the node instead of dropping
it — the hook D2H-extracts the node's pages (data **and** per-page quant
scales, so int8/fp8 entries spill at their quantized density) into a host-RAM
ring under its own byte budget, the node's page references are released, and
the node stays in the radix tree with ``tier == "host"`` holding the payload.
A later radix hit against a spilled node *promotes* it: the engine allocates
fresh pages, H2D-installs the payload behind the in-flight decode window, and
calls :meth:`promote_node` to re-admit the node to the device tier.  An
optional disk ring (``disk_capacity_bytes`` + ``disk_dir``) sits behind the
host ring: host-tier LRU victims whose payload has landed host-side are
written out instead of dropped.  Each tier runs its own leaf-only LRU; pinned
nodes never demote out of their tier, and a spilled chain is always a suffix —
a device node's ancestors are device-resident, so any matched chain is
``device* host* disk*`` in order.

All of this is host-side bookkeeping; the only device work a cache hit costs
is an H2D install per *spilled* chunk — device-tier hits are zero-copy.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from ..telemetry import MetricsRegistry, get_registry

#: Seed for the root prefix hash (djb2's seed; any odd constant works).
_HASH_SEED = 5381
#: Large Mersenne prime modulus keeps the rolling hash in cheap python ints.
_HASH_MOD = (1 << 61) - 1
_HASH_MULT = 1_000_003


def rolling_hash(prev: int, tokens) -> int:
    """Extend prefix hash ``prev`` over ``tokens`` (order-sensitive).

    ``rolling_hash(rolling_hash(seed, a), b) == rolling_hash(seed, a + b)`` —
    a node's key is the hash of its *entire* prefix, computed incrementally
    from its parent's key.
    """
    h = int(prev)
    for t in np.asarray(tokens).ravel().tolist():
        h = (h * _HASH_MULT + int(t) + 1) % _HASH_MOD
    return h


class PrefixNode:
    """One cached chunk: token ids + the retained KV as physical page ids
    into the shared page pool (``pages``; see :mod:`.paging`).  A node holds
    one allocator reference per page for as long as it is device-tier
    resident; a spilled node (``tier != "device"``) holds no pages and keeps
    its KV in ``host`` instead — a tuple of per-layer page/scale arrays (still
    device handles while the D2H extract is in flight, host ndarrays once the
    drain lands it) or, for the disk tier, the path of the ring file."""

    __slots__ = ("key", "tokens", "parent", "children", "pages",
                 "nbytes", "refs", "last_used", "tier", "host")

    def __init__(self, key: int, tokens: Optional[np.ndarray], parent,
                 pages: Optional[Tuple[int, ...]] = None, nbytes: int = 0):
        self.key = key
        self.tokens = tokens                 # [chunk] int32; None for the root
        self.parent = parent
        self.children: Dict[int, "PrefixNode"] = {}
        self.pages = pages                   # physical page ids; None once spilled
        self.nbytes = int(nbytes)
        self.refs = 0
        self.last_used = 0
        self.tier = "device"                 # "device" | "host" | "disk"
        self.host = None                     # spilled payload (tier != device)

    def __repr__(self) -> str:  # debugging aid only
        n = 0 if self.tokens is None else len(self.tokens)
        return (f"PrefixNode(len={n}, tier={self.tier}, refs={self.refs}, "
                f"children={len(self.children)}, bytes={self.nbytes})")


class PrefixCache:
    """Host-managed radix cache of KV page references with LRU byte budgeting.

    Parameters
    ----------
    capacity_bytes: retained-page budget (device tier).  Pinned (``refs > 0``)
        nodes never evict, so in-flight requests can transiently hold the
        cache over budget; eviction restores it as soon as pins release.
    registry: metrics registry for the ``serve/prefix_*`` gauges and the
        eviction/spill/promotion counters (default: the process registry).
    on_evict: called with each node as it leaves the cache *entirely* — the
        engine uses this to drop the allocator references the node's pages
        hold (the pages themselves survive while lanes still alias them;
        refcounting, not residency in this tree, decides when HBM is
        reclaimed).  A demotion to the host ring is NOT an eviction: the
        engine's ``spill`` hook releases the page refs itself.
    host_capacity_bytes: host-RAM spill ring budget; 0 disables tiering and
        restores drop-on-evict behavior exactly.
    spill: ``spill(node) -> payload | None`` — the engine hook that
        D2H-extracts a device-tier node's pages (returning the payload the
        node will carry) and releases its page references.  ``None`` means
        the node cannot be spilled and is dropped instead.
    disk_capacity_bytes / disk_dir: optional disk ring behind the host ring;
        host-tier LRU victims with landed payloads demote into ``.npz`` files
        under ``disk_dir`` instead of dropping.
    """

    def __init__(self, capacity_bytes: int,
                 registry: Optional[MetricsRegistry] = None,
                 on_evict=None,
                 host_capacity_bytes: int = 0,
                 spill=None,
                 disk_capacity_bytes: int = 0,
                 disk_dir: Optional[str] = None):
        self.on_evict = on_evict
        self.spill = spill
        self.capacity = int(capacity_bytes)
        if self.capacity <= 0:
            raise ValueError(f"capacity_bytes must be positive, got {capacity_bytes}")
        self.host_capacity = int(host_capacity_bytes or 0)
        self.disk_capacity = int(disk_capacity_bytes or 0)
        self.disk_dir = disk_dir
        if self.disk_capacity > 0 and not disk_dir:
            raise ValueError("disk_capacity_bytes > 0 requires disk_dir")
        self.root = PrefixNode(_HASH_SEED, None, None)
        self.bytes = 0
        self.host_bytes = 0
        self.disk_bytes = 0
        self.evictions = 0
        self.host_evictions = 0
        self.spills = 0
        self.promotions = 0
        self._nodes: List[PrefixNode] = []
        self._host_nodes: List[PrefixNode] = []
        self._disk_nodes: List[PrefixNode] = []
        self._disk_seq = 0
        self._clock = 0
        registry = registry if registry is not None else get_registry()
        self._bytes_gauge = registry.gauge(
            "serve/prefix_cache_bytes", help="retained prefix KV page bytes"
        )
        self._nodes_gauge = registry.gauge(
            "serve/prefix_cache_nodes", help="resident prefix cache nodes"
        )
        self._host_bytes_gauge = registry.gauge(
            "serve/prefix_host_bytes",
            help="prefix KV bytes resident in the host-RAM spill ring",
        )
        self._evict_counter = registry.counter(
            "serve/prefix_cache_evictions_total",
            help="prefix cache nodes dropped by LRU eviction",
        )
        self._spill_counter = registry.counter(
            "serve/prefix_spills_total",
            help="prefix nodes demoted device -> host spill ring",
        )
        self._promote_counter = registry.counter(
            "serve/prefix_promotions_total",
            help="spilled prefix nodes re-admitted to the device tier",
        )

    # ---------------------------------------------------------------- lookup
    def _touch(self, node: PrefixNode) -> None:
        self._clock += 1
        node.last_used = self._clock

    def match(self, prompt: np.ndarray,
              chunks: Sequence[Tuple[int, int]]) -> List[PrefixNode]:
        """Longest chain of cached nodes covering ``prompt``'s leading chunks.

        Walks ``chunks`` (the request's :func:`plan_chunks` plan) from the
        root; stops at the first partial chunk (``valid < bucket`` — padded
        chunks are never cached) or the first miss.  Matched nodes are
        LRU-touched but NOT pinned — callers pin via :meth:`acquire`.  A chain
        may cross tiers (``device* host* disk*`` — spilling is leaf-first, so
        spilled nodes are always a suffix); spilled nodes hit like device
        nodes and the engine promotes them at admission.
        """
        prompt = np.asarray(prompt)
        nodes: List[PrefixNode] = []
        node, start = self.root, 0
        for bucket, valid in chunks:
            if valid != bucket:
                break
            tokens = prompt[start:start + bucket]
            child = node.children.get(rolling_hash(node.key, tokens))
            if child is None or not np.array_equal(child.tokens, tokens):
                break
            self._touch(child)
            nodes.append(child)
            node, start = child, start + bucket
        return nodes

    # --------------------------------------------------------------- pinning
    def acquire(self, nodes: Iterable[PrefixNode]) -> None:
        """Pin ``nodes`` against eviction (a request depends on their pages)."""
        for n in nodes:
            n.refs += 1

    def release(self, nodes: Iterable[PrefixNode]) -> None:
        """Drop pins taken by :meth:`acquire`; touched so fresh users rank hot."""
        for n in nodes:
            n.refs -= 1
            if n.refs < 0:
                raise RuntimeError(f"prefix cache refcount underflow on {n!r}")
            self._touch(n)

    # -------------------------------------------------------------- mutation
    def insert_pages(self, parent: Optional[PrefixNode], tokens,
                     page_ids: Sequence[int], nbytes: int
                     ) -> Optional[PrefixNode]:
        """Retain one freshly prefilled chunk under ``parent`` (None = root)
        as *page references*: zero copies — the lane's own pages are aliased,
        the caller takes one allocator ref per page iff a NEW node was created
        OR a spilled node was re-admitted in place, which it detects by
        ``node.pages == tuple(page_ids)``.

        Returns the resident node — the existing one on an exact re-insert,
        whose ``pages`` will differ from ``page_ids`` unless the re-insert
        healed a spilled node — or ``None`` when the chunk cannot be retained
        (the byte budget cannot be met even after eviction, or a hash
        collision with a different token sequence occupies the key; both
        leave the cache untouched, and the caller must then stop extending
        this chain).
        """
        parent = parent if parent is not None else self.root
        tokens = np.ascontiguousarray(np.asarray(tokens, np.int32))
        key = rolling_hash(parent.key, tokens)
        existing = parent.children.get(key)
        if existing is not None:
            if np.array_equal(existing.tokens, tokens):
                self._touch(existing)
                if existing.tier != "device":
                    # a degraded promotion re-prefilled this chunk: fold the
                    # fresh pages back in so the node heals to device tier
                    self._readmit(existing, page_ids, int(nbytes))
                return existing
            return None  # 61-bit hash collision: keep the resident entry
        if not self._make_room(int(nbytes)):
            return None
        node = PrefixNode(key, tokens, parent,
                          pages=tuple(int(p) for p in page_ids), nbytes=nbytes)
        self._touch(node)
        parent.children[key] = node
        self._nodes.append(node)
        self.bytes += node.nbytes
        self._publish()
        return node

    def evict_one(self) -> bool:
        """Force one LRU device-tier eviction (page-pressure reclaim in the
        engine) — a demotion to the host ring when tiering is on, a drop
        otherwise; either way the node's page refs are released.  Returns
        False when nothing is evictable."""
        skip: set = set()
        while True:
            victim = self._lru_device_victim(skip)
            if victim is None:
                return False
            if self._evict(victim):
                return True
            skip.add(id(victim))

    def flush(self) -> int:
        """Drop every unpinned node from EVERY tier, leaf-first (interior
        nodes become leaves as their children go).  The weight hot-swap path
        calls this: retained KV was computed under the OLD weights, and
        replaying it after a swap would splice stale activations into fresh
        prefill — token corruption no output check downstream could attribute.
        Spilled tiers are purged too (never demoted: stale KV must not survive
        anywhere).  Pinned nodes (``refs > 0``) survive; callers drop queued
        requests' pins first (:meth:`Scheduler.drop_cache_pins`).  Returns
        nodes removed."""
        before = len(self._nodes) + len(self._host_nodes) + len(self._disk_nodes)
        skip: set = set()
        while True:
            victim = self._lru_device_victim(skip)
            if victim is None:
                break
            if not self._drop_subtree(victim):
                skip.add(id(victim))
        for nodes, drop in ((self._host_nodes, self._drop_host),
                            (self._disk_nodes, self._drop_disk)):
            skip = set()
            while True:
                victim = self._lru_leaf(nodes, skip)
                if victim is None:
                    break
                drop(victim)
        return before - (len(self._nodes) + len(self._host_nodes)
                         + len(self._disk_nodes))

    # ------------------------------------------------------------- promotion
    def node_payload(self, node: PrefixNode):
        """The spilled KV payload for promotion: the engine-provided spill
        value for host-tier nodes (device handles while the extract is in
        flight, host arrays once landed), or the arrays reloaded from the
        disk ring.  ``None`` when the node is not spilled or the ring file
        is gone."""
        if node.tier == "host":
            return node.host
        if node.tier == "disk":
            try:
                with np.load(node.host) as z:
                    return tuple(z[k] for k in z.files)
            except (OSError, ValueError):
                return None
        return None

    def settle_payload(self, node: PrefixNode, arrays) -> None:
        """Replace a host-tier node's in-flight device handles with the landed
        host arrays (the engine calls this from the drain side)."""
        if node.tier == "host":
            node.host = arrays

    def discard_spilled(self, node: PrefixNode) -> None:
        """Drop a spilled node (and its spilled subtree) whose payload can no
        longer be trusted — e.g. the spill gather failed to land.  No-op for
        device-tier or already-detached nodes."""
        if node.tier == "device" or node.key not in node.parent.children:
            return
        self._drop_subtree(node)

    def promote_node(self, node: PrefixNode, page_ids: Sequence[int]) -> bool:
        """Record a successful H2D promotion of a spilled node and try to
        re-admit it to the device tier with the freshly installed pages.  The
        caller (engine) has already scatter-installed the payload into
        ``page_ids`` — that promotion counts regardless — and takes one
        allocator ref per page iff this returns True (re-admission
        succeeded).  Re-admission fails, with the node staying spilled and
        its payload kept for the next hit, when the parent is not
        device-resident or the device byte budget cannot be met (e.g. every
        resident node is pinned by a running lane) — the lane still owns its
        pages either way, only cache retention is lost."""
        if node.tier == "device":
            return False
        self.promotions += 1
        self._promote_counter.inc()
        if not self._readmit(node, page_ids, node.nbytes):
            return False
        self._touch(node)
        return True

    def _readmit(self, node: PrefixNode, page_ids: Sequence[int],
                 nbytes: int) -> bool:
        """host/disk -> device transition in place (shared by promotion and
        the degraded-promotion heal in :meth:`insert_pages`)."""
        if node.parent.tier != "device":
            return False  # keep the device* host* disk* chain ordering
        if not self._make_room(int(nbytes)):
            return False
        if node.tier == "host":
            self._host_nodes.remove(node)
            self.host_bytes -= node.nbytes
        else:
            self._disk_nodes.remove(node)
            self.disk_bytes -= node.nbytes
            self._unlink_disk(node)
        node.host = None
        node.tier = "device"
        node.pages = tuple(int(p) for p in page_ids)
        node.nbytes = int(nbytes)
        self._nodes.append(node)
        self.bytes += node.nbytes
        self._publish()
        return True

    # -------------------------------------------------------------- eviction
    def _make_room(self, nbytes: int) -> bool:
        """Evict LRU unpinned device leaves until ``nbytes`` more fits; False
        if the survivors (pinned or interior) can't shrink far enough."""
        if nbytes > self.capacity:
            return False
        skip: set = set()
        while self.bytes + nbytes > self.capacity:
            victim = self._lru_device_victim(skip)
            if victim is None:
                return False
            if not self._evict(victim):
                skip.add(id(victim))
        return True

    def _lru_device_victim(self, skip=()) -> Optional[PrefixNode]:
        """LRU unpinned device node with no device-tier children.  Spilled
        children don't shield a parent from eviction — the parent spills too
        (keeping the chain ordering) or the whole spilled subtree drops."""
        victim = None
        for n in self._nodes:
            if n.refs > 0 or id(n) in skip:
                continue
            if any(c.tier == "device" for c in n.children.values()):
                continue
            if victim is None or n.last_used < victim.last_used:
                victim = n
        return victim

    @staticmethod
    def _lru_leaf(nodes: List[PrefixNode], skip=()) -> Optional[PrefixNode]:
        victim = None
        for n in nodes:
            if n.refs > 0 or n.children or id(n) in skip:
                continue
            if victim is None or n.last_used < victim.last_used:
                victim = n
        return victim

    def _evict(self, node: PrefixNode) -> bool:
        """Demote ``node`` to the host ring when tiering allows; drop it (and
        any spilled descendants) otherwise.  False when neither is possible
        (e.g. a pinned spilled descendant)."""
        if (self.host_capacity > 0 and self.spill is not None
                and self._demote(node)):
            return True
        return self._drop_subtree(node)

    def _demote(self, node: PrefixNode) -> bool:
        """device -> host transition: make host-ring room first, then run the
        engine's D2H spill hook.  Page refs are released by the hook."""
        if node.nbytes > self.host_capacity:
            return False
        while self.host_bytes + node.nbytes > self.host_capacity:
            victim = self._lru_leaf(self._host_nodes)
            if victim is None:
                return False
            self._remove_host(victim)
        payload = self.spill(node)
        if payload is None:
            return False
        node.host = payload
        node.tier = "host"
        node.pages = None
        self._nodes.remove(node)
        self.bytes -= node.nbytes
        self._host_nodes.append(node)
        self.host_bytes += node.nbytes
        self.spills += 1
        self._spill_counter.inc()
        self._publish()
        return True

    def _drop_subtree(self, node: PrefixNode) -> bool:
        """Drop ``node`` and its spilled descendants leaf-first (a device
        victim may carry host/disk children); refuses — removing nothing —
        when any descendant is pinned."""
        stack, order = [node], []
        while stack:
            n = stack.pop()
            if n.refs > 0:
                return False
            order.append(n)
            stack.extend(n.children.values())
        for n in reversed(order):
            if n.tier == "device":
                self._remove(n)
            elif n.tier == "host":
                self._drop_host(n)
            else:
                self._drop_disk(n)
        return True

    def _remove(self, node: PrefixNode) -> None:
        del node.parent.children[node.key]
        self._nodes.remove(node)
        self.bytes -= node.nbytes
        self.evictions += 1
        self._evict_counter.inc()
        self._publish()
        if self.on_evict is not None:
            self.on_evict(node)

    def _remove_host(self, node: PrefixNode) -> None:
        """Host-ring victim: demote to the disk ring when possible, drop
        otherwise."""
        if self._disk_admit(node):
            return
        self._drop_host(node)

    def _drop_host(self, node: PrefixNode) -> None:
        del node.parent.children[node.key]
        self._host_nodes.remove(node)
        self.host_bytes -= node.nbytes
        node.host = None
        node.tier = "device"  # detached; neutral state for late settles
        self.host_evictions += 1
        self.evictions += 1
        self._evict_counter.inc()
        self._publish()
        if self.on_evict is not None:
            self.on_evict(node)

    def _disk_admit(self, node: PrefixNode) -> bool:
        """host -> disk transition for a landed payload; in-flight payloads
        (still device handles) and oversized nodes are not disk-eligible."""
        if self.disk_capacity <= 0 or node.children or node.nbytes > self.disk_capacity:
            return False
        payload = node.host
        if not (isinstance(payload, tuple)
                and payload
                and all(isinstance(a, np.ndarray) for a in payload)):
            return False
        while self.disk_bytes + node.nbytes > self.disk_capacity:
            victim = self._lru_leaf(self._disk_nodes)
            if victim is None:
                return False
            self._drop_disk(victim)
        self._disk_seq += 1
        path = os.path.join(self.disk_dir,
                            f"prefix_{node.key:016x}_{self._disk_seq}.npz")
        try:
            np.savez(path, *payload)
        except OSError:
            return False
        node.host = path
        node.tier = "disk"
        self._host_nodes.remove(node)
        self.host_bytes -= node.nbytes
        self._disk_nodes.append(node)
        self.disk_bytes += node.nbytes
        self._publish()
        return True

    def _drop_disk(self, node: PrefixNode) -> None:
        del node.parent.children[node.key]
        self._disk_nodes.remove(node)
        self.disk_bytes -= node.nbytes
        self._unlink_disk(node)
        node.host = None
        node.tier = "device"  # detached; neutral state for late settles
        self.evictions += 1
        self._evict_counter.inc()
        if self.on_evict is not None:
            self.on_evict(node)

    def _unlink_disk(self, node: PrefixNode) -> None:
        try:
            os.remove(node.host)
        except (OSError, TypeError):
            pass

    def _publish(self) -> None:
        self._bytes_gauge.set(self.bytes)
        self._nodes_gauge.set(len(self._nodes))
        self._host_bytes_gauge.set(self.host_bytes)

    # ----------------------------------------------------------------- stats
    @property
    def num_nodes(self) -> int:
        return len(self._nodes)

    def stats(self) -> Dict[str, Any]:
        """Plain-dict snapshot for the engine's stats surface."""
        return {
            "capacity_bytes": self.capacity,
            "bytes": self.bytes,
            "nodes": len(self._nodes),
            "evictions": self.evictions,
            "host_capacity_bytes": self.host_capacity,
            "host_bytes": self.host_bytes,
            "host_nodes": len(self._host_nodes),
            "host_evictions": self.host_evictions,
            "disk_bytes": self.disk_bytes,
            "disk_nodes": len(self._disk_nodes),
            "spills": self.spills,
            "promotions": self.promotions,
        }


__all__ = ["PrefixCache", "PrefixNode", "rolling_hash"]
