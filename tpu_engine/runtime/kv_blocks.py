"""Paged KV cache: a static block pool, page tables, and a radix tree of
shared prompt prefixes.

The continuous scheduler's dense cache reserves `max_seq` columns of HBM
per slot the moment a row is admitted — a 24-token chat request pins the
same memory as a 1024-token one, and KV can only be reused on an exact
whole-prompt repeat (`_PrefixCache`). This module replaces that with the
vLLM-style layout, kept TPU-native:

- **One static device tensor** per K/V of shape
  ``(L, num_blocks, block_size, H_kv*D)`` (`BlockPool` states the
  layout) — allocated once, donated through every step and updated in
  place there, so the layout stays compiler-visible and nothing
  retraces as rows come and go (PAPERS.md "Compiler-First … Portable
  O(1) Autoregressive Caching").
  Block 0 is the reserved **null block**: unallocated page-table entries
  point at it, padding scatters dump into it, and it is never attended
  (the position mask ends at each row's `pos`).
- **Host-side bookkeeping** (free list, per-block refcounts, the radix
  tree) under one lock. The lock ALSO serializes device dispatches that
  touch the pool: decode chunks donate the pool buffers, and the prefill
  thread's prefix gathers read them — dispatch order under the lock is
  what keeps a gather from racing a donation (same-device programs
  execute in dispatch order).
- **Radix tree over token blocks**: each node is one FULL block of
  ``block_size`` prompt tokens, keyed by those tokens, holding a
  refcount on its pool block. A new prompt walks the tree and maps every
  matched full block straight into its page table (refcount++, zero
  prefill compute); prefill resumes mid-prompt after the match. Nodes
  are inserted at admission for each full prompt block, so ANY shared
  prefix — not just exact repeats — is shared, across requests and
  buckets (paged rows are 0-aligned: token `i` always lives at logical
  column `i`).
- **Refcounts + copy-on-write**: a block is freed only at refcount 0
  (row released AND no tree node). Rows only ever append into blocks
  they exclusively own — full shared blocks are read-only by
  construction — but ``ensure_writable`` enforces it mechanically:
  writing into a block with refcount > 1 first copies it (one jitted
  dynamic-slice copy) and swaps the writer's reference.
- **Eviction**: when allocation runs dry, LRU radix LEAVES whose blocks
  have refcount 1 (tree-only) are evicted until enough blocks free. A
  block referenced by any live row is structurally unevictable — its
  refcount is ≥ 2 while a tree node points at it.
- **Quantized block payloads** (``quantize="int8"``): the pool tensors
  store int8 instead of bf16, with one f32 scale per (layer, block
  slot, kv-head) vector held in matching ``(L, num_blocks, bs, H_kv)``
  arrays that live beside the free-list under the SAME pool lock,
  refcount lifecycle, COW, radix sharing, and generation stamps.
  Quantization happens exactly ONCE, at block write (admission scatter
  / in-dispatch prefill-chunk and decode-append writes in
  models.transformer); every later movement — COW ``ensure_writable``,
  radix re-adoption, host-tier demotion and swap-in — copies int8 +
  scale verbatim, so there is no cumulative requantization drift and a
  demote/promote round trip stays bit-exact. The per-slot scale
  granularity is what makes write-once possible: a single-token decode
  append quantizes only its own vector (a per-block scale would force
  clipping or requantizing neighbours). ``ops.paged_attention``'s
  quantized read paths apply the scales inside the kernel (fused
  dequant), so HBM traffic is int8 — about half the bf16 bytes per
  block, which is the ~2x capacity multiplier (and the host tier's 2x
  swap-bandwidth win) on the same memory budget.
- **Hierarchical host tier** (``host_blocks`` > 0): instead of
  destroying a cold radix leaf, eviction DEMOTES its block to a pinned
  host-RAM buffer — the node stays in the tree, keyed and matchable,
  holding a host slot instead of a device block. A later radix hit on a
  demoted node SWAPS the block back in (one jitted host→device write,
  dispatched asynchronously on the prefill thread) instead of
  recomputing that prefix's prefill. Promotion takes free blocks first
  and may DISPLACE LRU-colder resident leaves (demoting them to this
  same tier — the just-requested prefix is hotter by definition, and no
  cached state is destroyed while the tier has room), but must always
  leave ``promote_reserve`` free blocks behind (live-row growth
  outranks resurrection of cold prefixes); when the reserve cannot be
  met the lookup simply stops at the resident prefix (counted
  ``swap_in_deferred``). A full host tier makes room by destroying its
  own LRU demoted leaves. The copies are verbatim dtype-preserving
  moves, so a demote/promote round trip is bit-exact.

- **Chain export/import** (``export_chain`` / ``import_chain``): a
  row's block chain serialized as a JSON-safe wire dict — verbatim
  dtype-preserving payload bytes per block (int8 + scale travel
  together, never requantized; demoted nodes export straight from
  their pinned host buffers, no swap-in), a crc32 checksum over the
  whole chain, and the source pool's generation stamp. This is the
  "serialize blocks over the wire" primitive of ROADMAP open item 4;
  live stream migration (DESIGN.md) is its first consumer.

`runtime.scheduler.ContinuousGenerator(kv_block_size=...)` drives this;
`ops.paged_attention` is the matching attention read path.
"""

from __future__ import annotations

import base64
import threading
import zlib
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tpu_engine.models.transformer import TransformerConfig
from tpu_engine.ops.attention import KVCache


def dense_block_bytes(cfg: TransformerConfig, block_size: int, dtype) -> int:
    """HBM bytes one block costs at a full-precision `dtype` — the
    single source of the pool-layout formula (BlockPool.stats() and the
    bench's equal-byte-budget sizing must never disagree). The lanes a
    token takes are what the model STATES (`cfg.kv_lanes`): K and V of
    every KV head at the model's head width, or a latent and its rope
    key — never `d_model / n_heads` assumed here."""
    return int(cfg.n_layers * block_size * sum(cfg.kv_lanes)
               * jnp.dtype(dtype).itemsize)


def quant_block_bytes(cfg: TransformerConfig, block_size: int) -> int:
    """Bytes of one quantized block: int8 K+V payload plus the f32 scale
    per (layer, slot, kv-head) vector — `2·L·bs·H_kv·(D + 4)`."""
    slot_heads = cfg.n_layers * block_size * cfg.kv_heads
    return int(2 * slot_heads * (cfg.d_head + 4))


class PoolExhausted(RuntimeError):
    """Raised when an allocation cannot be satisfied even after evicting
    every evictable radix leaf — callers back off (defer the admission)
    or complete the starved row early; they must never treat this as a
    device failure."""


class _RadixNode:
    __slots__ = ("children", "parent", "key", "block_id", "last_used",
                 "host_slot")

    def __init__(self, parent: Optional["_RadixNode"], key, block_id: int):
        self.children: Dict[tuple, _RadixNode] = {}
        self.parent = parent
        self.key = key            # the block's token tuple (len block_size)
        self.block_id = block_id  # -1: root, or a DEMOTED node (host tier)
        self.last_used = 0
        self.host_slot = -1       # >= 0 while demoted to the host tier

    @property
    def demoted(self) -> bool:
        return self.host_slot >= 0


class RadixTree:
    """Prefix index over FULL token blocks. One node per (path, block of
    tokens); the node's pool block holds exactly those tokens' KV at
    logical columns [depth*bs, (depth+1)*bs). All methods assume the
    owning pool's lock is held."""

    def __init__(self, pool: "BlockPool"):
        self._pool = pool
        self.root = _RadixNode(None, None, -1)
        self.nodes = 0
        self._clock = 0

    def _tick(self) -> int:
        self._clock += 1
        return self._clock

    def _full_blocks(self, tokens: Sequence[int]) -> List[tuple]:
        bs = self._pool.block_size
        return [tuple(tokens[i:i + bs])
                for i in range(0, (len(tokens) // bs) * bs, bs)]

    def lookup(self, tokens: Sequence[int],
               promote_reserve: Optional[int] = None) -> List[int]:
        """Longest-prefix match over full blocks. Returns the matched
        block ids IN ORDER, each retained once on behalf of the caller
        (release them when the row frees — or immediately on a discarded
        admission).

        ``promote_reserve``: when not None, a match reaching a DEMOTED
        node (host tier) swaps its block back onto the device instead of
        treating it as a miss — displacing LRU-colder resident leaves if
        the free list is short, provided the pool keeps at least that
        many free blocks after the promotion (live-row growth must never
        be starved by cold-prefix resurrection; a refused promotion ends
        the match at the resident prefix and counts
        ``swap_in_deferred``). None (default) never promotes — direct
        callers and the sharing-off path keep the pre-tier behavior."""
        pool = self._pool
        pool.radix_lookups += 1
        ids: List[int] = []
        node = self.root
        stamp = self._tick()
        promoted = 0
        for key in self._full_blocks(tokens):
            child = node.children.get(key)
            if child is None:
                break
            if child.demoted:
                if promote_reserve is None or not pool._promote_node(
                        child, promote_reserve):
                    if promote_reserve is not None:
                        pool.swap_in_deferred += 1
                    break
                promoted += 1
            child.last_used = stamp
            pool.retain(child.block_id)
            ids.append(child.block_id)
            node = child
        if promoted:
            pool.swap_in_events += 1
        if ids:
            pool.radix_hits += 1
        return ids

    def insert(self, tokens: Sequence[int], block_ids: Sequence[int]) -> int:
        """Index a row's full prompt blocks. ``block_ids[j]`` is the pool
        block holding prompt block j (the row's page-table prefix). New
        nodes retain their block (the tree's own reference); existing
        nodes are left pointing at their original block — the newcomer's
        duplicate block simply stays row-private. A DEMOTED node is
        re-adopted instead: the newcomer's block holds exactly these
        tokens' freshly recomputed KV, so the node points at it and its
        host slot frees (the device copy is strictly better — no swap-in
        needed on the next hit). Returns nodes added."""
        added = 0
        node = self.root
        stamp = self._tick()
        for j, key in enumerate(self._full_blocks(tokens)):
            child = node.children.get(key)
            if child is None:
                child = _RadixNode(node, key, int(block_ids[j]))
                node.children[key] = child
                self._pool.retain(child.block_id)
                self.nodes += 1
                added += 1
            elif child.demoted:
                self._pool._host_free.append(child.host_slot)
                child.host_slot = -1
                child.block_id = int(block_ids[j])
                self._pool.retain(child.block_id)
            child.last_used = stamp
            node = child
        return added

    def _evictable(self) -> List[_RadixNode]:
        """Nodes whose DEVICE block the tree alone references and whose
        children (if any) are all demoted — the device-resident frontier
        of each branch, so demotion can proceed root-ward leaf by leaf."""
        out, stack = [], [self.root]
        while stack:
            n = stack.pop()
            for c in n.children.values():
                if c.children:
                    stack.append(c)
                if c.demoted:
                    continue
                if (all(g.demoted for g in c.children.values())
                        and self._pool.refcount(c.block_id) == 1):
                    out.append(c)  # device frontier, tree-only reference
        return out

    def _demoted_leaves(self) -> List[_RadixNode]:
        out, stack = [], [self.root]
        while stack:
            n = stack.pop()
            for c in n.children.values():
                if c.children:
                    stack.append(c)
                elif c.demoted:
                    out.append(c)
        return out

    def chain_nodes(self, tokens: Sequence[int]) -> List["_RadixNode"]:
        """Longest-prefix node chain for ``tokens`` WITHOUT promoting,
        pinning, or stamping anything — a demoted node simply stays in
        the chain (its KV is read from the host tier). The export side
        of migration uses this to serialize a cached prefix exactly as
        it sits, device or host, with zero swap-in traffic."""
        out: List[_RadixNode] = []
        node = self.root
        for key in self._full_blocks(tokens):
            child = node.children.get(key)
            if child is None:
                break
            out.append(child)
            node = child
        return out

    def top_chains(self, top_k: int = 8, max_tokens: int = 256) -> List[dict]:
        """The K deepest root-to-leaf chains as compact
        ``{"tokens", "blocks"}`` summaries — the fleet prefix tier's
        /health seed (a gateway prober recomputes its affinity
        fingerprint from the leading tokens, so no fingerprint scheme
        leaks into the pool). Bounded: at most ``top_k`` entries of at
        most ``max_tokens`` tokens each, never a full-tree dump.
        Demoted nodes count like resident ones (export serves both).
        Caller holds the pool lock."""
        leaves: List[tuple] = []
        stack = [(self.root, 0)]
        while stack:
            n, d = stack.pop()
            if not n.children:
                if d:
                    leaves.append((d, n))
                continue
            for c in n.children.values():
                stack.append((c, d + 1))
        leaves.sort(key=lambda t: (-t[0], -t[1].last_used))
        out: List[dict] = []
        for depth, leaf in leaves[:max(0, int(top_k))]:
            keys = []
            node = leaf
            while node is not None and node.key is not None:
                keys.append(node.key)
                node = node.parent
            keys.reverse()
            toks: List[int] = []
            for key in keys:
                toks.extend(int(t) for t in key)
                if len(toks) >= max_tokens:
                    break
            out.append({"tokens": toks[:max_tokens], "blocks": int(depth)})
        return out

    def evict(self, n_blocks: int) -> int:
        """Free up to ``n_blocks`` pool blocks by demoting (host tier
        configured) or dropping LRU leaves whose blocks nothing but the
        tree references. Never touches a block a live row OR a pinned
        lookup holds (refcount ≥ 2). Returns device blocks freed."""
        freed = 0
        while freed < n_blocks:
            leaves = self._evictable()
            if not leaves:
                break
            leaves.sort(key=lambda n: n.last_used)
            for leaf in leaves:
                if freed >= n_blocks:
                    break
                if self._pool._demote_leaf(leaf):
                    freed += 1  # node survives in the tree, demoted
                    continue
                del leaf.parent.children[leaf.key]
                self._pool.release(leaf.block_id)
                self.nodes -= 1
                self._pool.evictions += 1
                freed += 1
        return freed

    def clear(self) -> None:
        """Drop every node (weight reload: cached KV is stale). Blocks
        still referenced by live rows survive until those rows free;
        demoted nodes' host slots free immediately (stale KV)."""
        stack = [self.root]
        while stack:
            n = stack.pop()
            for c in n.children.values():
                stack.append(c)
                if c.demoted:
                    self._pool._host_free.append(c.host_slot)
                    c.host_slot = -1
                else:
                    self._pool.release(c.block_id)
        self.root = _RadixNode(None, None, -1)
        self.nodes = 0


class BlockPool:
    """Device block pool + host bookkeeping for the paged KV cache.

    **What a block holds, by kind of pool.** Dense (every kv_paged
    model): ``block_size`` tokens' keys in ``caches.k`` and values in
    ``caches.v``, all KV heads, layout below. Quantized: the same in
    int8 plus a scale a (slot, KV head). Latent (the kv_latent family,
    models.moonlight, and the MLA layers of a kv_and_state model,
    models.kimi_linear): ``caches.k`` holds each token's shared key
    lanes (the rotated rope key, or plain lanes where nothing is
    rotated), zero-padded to one lane tile, and ``caches.v`` its
    normalised latent — ``cfg.kv_lanes`` = (128, kv_lora_rank); free
    list, refcounts, radix tree, tables and copy-on-write treat the pair
    as they treat K and V, while the host tier, int8 and the chain wire
    format, which assume two tensors of ``H_kv*D`` lanes, are refused
    for it at start-up (runtime.scheduler).

    **The dense pool's layout, stated once.** ``caches`` is a K/V pair of
    ``(L, num_blocks, block_size, H_kv*D)`` tensors: the two minor axes
    are a block's slots and, merged, its KV heads — head ``h`` in lanes
    ``[h*D, (h+1)*D)``. It is the operand the paged-attention kernel
    reads (`ops.paged_attention._paged_call`: one ``(bs, H_kv*D)`` tile
    a block, picked by layer index and block table) and the carry the
    step functions scatter into (`models.transformer._write_pool`), so
    a tick neither reshapes nor copies it; a minor axis of ``H_kv*D``
    also tiles on the TPU where ``(H_kv, D)`` = (20, 64) does not. An
    int8 pool's payload has the same shape, its ``scales`` are
    ``(L, num_blocks, block_size, H_kv)`` f32. Under tensor parallelism
    the last axis of both shards in whole heads. One block on the host —
    a demoted block, a block of a chain on the wire — is
    ``(L, block_size, H_kv*D)``: the same bytes in the same order as the
    wire format's documented ``(L, block_size, H_kv, D)``."""

    def __init__(self, cfg: TransformerConfig, num_blocks: int,
                 block_size: int, dtype=jnp.bfloat16, device=None,
                 host_blocks: int = 0, quantize: str = "", mesh=None,
                 tp_axis: str = "model"):
        """``mesh`` (tensor-parallel serving, DESIGN.md "Tensor-parallel
        serving"): a 1-axis ``model`` mesh — the pool tensors shard
        their merged ``H_kv*D`` axis over it in whole heads (scale
        arrays their ``H_kv`` axis, for int8 pools), matching the
        heads-axis model placement so each tick's
        pool-donating dispatch stays one SPMD program with zero
        resharding. ``kv_heads`` must divide by the axis size. None
        (default) keeps today's single-device pool; ``device`` and
        ``mesh`` are mutually exclusive."""
        if num_blocks < 2:
            raise ValueError("need >= 2 blocks (block 0 is the null block)")
        if quantize not in ("", "int8"):
            raise ValueError(f"unsupported KV quantize mode {quantize!r} "
                             "(only 'int8')")
        self.tp = 1
        # NamedSharding of the payload pools AND the int8 scale arrays:
        # both carry their heads on the last of four axes.
        self.kv_sharding = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            if device is not None:
                raise ValueError("BlockPool: pass device OR mesh, not "
                                 "both (a mesh owns its own placement)")
            tp = int(mesh.shape[tp_axis])
            if cfg.kv_heads % tp:
                raise ValueError(
                    f"kv_heads={cfg.kv_heads} must divide by the "
                    f"tensor-parallel degree {tp} (the pool shards its "
                    f"H_kv*D axis in whole heads)")
            self.tp = tp
            self.kv_sharding = NamedSharding(
                mesh, P(None, None, None, tp_axis))
        self.cfg = cfg
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        # `io_dtype` is the pool's COMPUTE dtype — what gathers dequantize
        # to and what an unquantized pool stores; `_dtype` is the actual
        # payload storage dtype (int8 under quantization).
        self.quantized = quantize == "int8"
        self.io_dtype = dtype
        self._dtype = jnp.int8 if self.quantized else dtype
        self._device = device
        # One lock for bookkeeping AND pool-touching dispatch ordering
        # (module docstring). RLock: eviction runs inside alloc.
        self.lock = threading.RLock()
        # Bumped by reset(): pins taken against an older generation are
        # void (the refcount table was rebuilt wholesale) — holders must
        # compare generations instead of releasing stale ids.
        self.generation = 0
        # Quantized mode: per-(layer, block slot, kv-head) f32 scales in a
        # KVCache pair of (L, NB, bs, H_kv) arrays. They live beside the
        # free-list under the pool lock, move verbatim with their blocks
        # (COW / demote / promote), and are donated through every
        # pool-writing dispatch exactly like the payload tensors.
        self.scales: Optional[KVCache] = None
        self.caches = self._init_device()
        self._ref = np.zeros((self.num_blocks,), np.int32)
        self._ref[0] = 1  # null block: permanently pinned, never allocated
        self._free: List[int] = list(range(self.num_blocks - 1, 0, -1))
        self.radix = RadixTree(self)
        self._copy_exe = None
        self._promote_exe = None
        self._import_exe: Dict[int, object] = {}  # {n_blocks: chain write}
        # Hierarchical host tier (module docstring): pinned host buffers
        # for demoted radix blocks. Dtype matches the device pool exactly
        # so a demote/promote round trip is bit-identical.
        self.host_blocks = int(host_blocks)
        self._host_k = self._host_v = None
        self._host_free: List[int] = []
        self._promoting: Optional[_RadixNode] = None
        self._host_ks = self._host_vs = None
        if self.host_blocks > 0:
            hshape = (self.host_blocks, cfg.n_layers, self.block_size,
                      cfg.kv_heads * cfg.d_head)
            hdtype = jnp.zeros((), self._dtype).dtype  # numpy-compat dtype
            self._host_k = np.zeros(hshape, hdtype)
            self._host_v = np.zeros(hshape, hdtype)
            if self.quantized:
                # Scale slots pair 1:1 with host payload slots — a
                # demoted block's int8 bytes and its scale vectors travel
                # (and free) together, so the round trip is bit-exact.
                sshape = (self.host_blocks, cfg.n_layers, self.block_size,
                          cfg.kv_heads)
                self._host_ks = np.zeros(sshape, np.float32)
                self._host_vs = np.zeros(sshape, np.float32)
            self._host_free = list(range(self.host_blocks - 1, -1, -1))
        # Counters for /stats, /metrics, and the paged/affinity benches.
        self.prefix_hit_tokens = 0
        self.prefilled_tokens = 0
        self.evictions = 0
        self.cow_copies = 0
        self.radix_lookups = 0
        self.radix_hits = 0
        self.demotions = 0
        self.swap_ins = 0          # blocks promoted host -> device
        self.swap_in_events = 0    # lookups that promoted >= 1 block
        self.swap_in_deferred = 0  # promotions refused by the reserve rule
        self.host_evictions = 0    # demoted leaves destroyed (tier full)
        self.swapped_in_tokens = 0

    def _init_device(self) -> KVCache:
        cfg = self.cfg
        slots = (cfg.n_layers, self.num_blocks, self.block_size)
        # Tensor-parallel pool: committed head-sharded from birth, so
        # every consumer executable compiles SPMD over the mesh.
        where = self.kv_sharding or self._device

        def place(pair):
            return pair if where is None else jax.device_put(pair, where)

        k_lanes, v_lanes = cfg.kv_lanes
        caches = place(KVCache(jnp.zeros(slots + (k_lanes,), self._dtype),
                               jnp.zeros(slots + (v_lanes,), self._dtype)))
        if self.quantized:
            # Scale 1.0 everywhere: unwritten (and null-block) slots
            # dequantize to exact zeros, like a fresh bf16 pool.
            shape = slots + (cfg.kv_heads,)
            self.scales = place(KVCache(jnp.ones(shape, jnp.float32),
                                        jnp.ones(shape, jnp.float32)))
        return caches

    # -- bookkeeping (hold self.lock) -----------------------------------------

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    def refcount(self, block_id: int) -> int:
        return int(self._ref[block_id])

    def evictable_blocks(self) -> int:
        return len(self.radix._evictable())

    def can_alloc(self, n: int) -> bool:
        return n <= len(self._free) + self.evictable_blocks()

    def alloc(self, n: int) -> List[int]:
        """n fresh blocks (refcount 1 each), evicting radix leaves LRU
        when the free list runs short. Raises PoolExhausted (state
        unchanged) when even eviction cannot cover the request."""
        if n > len(self._free):
            self.radix.evict(n - len(self._free))
        if n > len(self._free):
            raise PoolExhausted(
                f"need {n} blocks, {len(self._free)} free and nothing "
                f"evictable ({self.num_blocks} total)")
        ids = [self._free.pop() for _ in range(n)]
        for i in ids:
            self._ref[i] = 1
        return ids

    def retain(self, block_id: int) -> None:
        assert self._ref[block_id] > 0, "retain of a free block"
        self._ref[block_id] += 1

    def release(self, block_id: int) -> None:
        if block_id == 0:
            return  # null block: permanent
        self._ref[block_id] -= 1
        assert self._ref[block_id] >= 0, "double free"
        if self._ref[block_id] == 0:
            self._free.append(block_id)

    def release_many(self, block_ids: Sequence[int]) -> None:
        for i in block_ids:
            self.release(i)

    def release_tail(self, block_list: List[int], keep: int) -> int:
        """Trim a row's block table IN PLACE to its first ``keep``
        entries, releasing the rest — the speculative scheduler's
        block-boundary rewind: blocks allocated for a verify window whose
        rejected tail (or shrinking token budget) moved past them return
        to the pool instead of idling on the row. Returns blocks
        released. Tail blocks are the row's private append blocks by
        construction; a radix-referenced block would simply drop to the
        tree's refcount and survive."""
        freed = 0
        while len(block_list) > max(0, int(keep)):
            self.release(block_list.pop())
            freed += 1
        return freed

    def ensure_writable(self, block_id: int) -> Tuple[int, bool]:
        """Copy-on-write: a caller about to APPEND into ``block_id``
        gets a private copy when anything else (tree node, other row)
        also references it. Returns (writable id, copied?). The caller
        swaps its page-table entry and drops its old reference; the
        scheduler's append path never actually shares (only full blocks
        enter the tree), so this is the mechanical guard for the
        invariant, exercised directly by tests."""
        if self._ref[block_id] <= 1:
            return block_id, False
        if self._copy_exe is None:
            def copy_pair(caches, src, dst):
                k = jax.lax.dynamic_slice_in_dim(caches.k, src, 1, axis=1)
                v = jax.lax.dynamic_slice_in_dim(caches.v, src, 1, axis=1)
                return KVCache(
                    jax.lax.dynamic_update_slice_in_dim(caches.k, k, dst,
                                                        axis=1),
                    jax.lax.dynamic_update_slice_in_dim(caches.v, v, dst,
                                                        axis=1))

            if self.quantized:
                # COW moves int8 payload AND scales verbatim — the copy
                # is a bit-exact clone, never a requantization.
                def copy_block(caches, scales, src, dst):
                    return (copy_pair(caches, src, dst),
                            copy_pair(scales, src, dst))

                self._copy_exe = jax.jit(copy_block, donate_argnums=(0, 1))
            else:
                self._copy_exe = jax.jit(copy_pair, donate_argnums=(0,))
        new_id = self.alloc(1)[0]
        if self.quantized:
            self.caches, self.scales = self._copy_exe(
                self.caches, self.scales,
                jnp.int32(block_id), jnp.int32(new_id))
        else:
            self.caches = self._copy_exe(self.caches, jnp.int32(block_id),
                                         jnp.int32(new_id))
        self.release(block_id)
        self.cow_copies += 1
        return new_id, True

    # -- host tier (hold self.lock) -------------------------------------------

    def _demote_leaf(self, leaf: "_RadixNode") -> bool:
        """Move a tree-only leaf's block to the host tier instead of
        destroying it: copy device→host (verbatim, dtype-preserving),
        free the device block, mark the node demoted. A full tier first
        destroys its own LRU demoted leaf to make room; still no room
        (tier disabled) → False, and the caller falls back to the
        destroy path. The device reads happen under the pool lock, so
        they order after every donation that produced the block."""
        if self.host_blocks <= 0:
            return False
        if not self._host_free:
            victims = [v for v in self.radix._demoted_leaves()
                       if v is not self._promoting]
            if not victims:
                return False  # demoted interior nodes only: can't destroy
            victims.sort(key=lambda n: n.last_used)
            v = victims[0]
            del v.parent.children[v.key]
            self._host_free.append(v.host_slot)
            v.host_slot = -1
            self.radix.nodes -= 1
            self.host_evictions += 1
        slot = self._host_free.pop()
        bid = leaf.block_id
        self._host_k[slot] = np.asarray(jax.device_get(self.caches.k[:, bid]))
        self._host_v[slot] = np.asarray(jax.device_get(self.caches.v[:, bid]))
        if self.quantized:
            # int8 payload + f32 scales move verbatim: the demoted copy
            # is bit-identical, never requantized.
            self._host_ks[slot] = np.asarray(
                jax.device_get(self.scales.k[:, bid]))
            self._host_vs[slot] = np.asarray(
                jax.device_get(self.scales.v[:, bid]))
        self.release(bid)
        leaf.block_id = -1
        leaf.host_slot = slot
        self.demotions += 1
        return True

    def _promote_node(self, node: "_RadixNode", reserve: int) -> bool:
        """Swap a demoted node's block back onto the device, then one
        jitted host→device block write (dispatched asynchronously; the
        pool lock orders it against decode-chunk donations exactly like
        a prefix gather). Block sourcing, in order: the free list, then
        DISPLACING LRU-colder resident leaves (evict() — which demotes
        them to this same tier, so no cached state is destroyed while
        the tier has room; the node being promoted was just requested,
        so it is by definition hotter than an LRU victim). Either way at
        least ``reserve`` free blocks must remain afterwards — live
        rows' growth and admissions outrank resurrecting a cold prefix
        (the pool-pressure rule the offload tests pin) — else the
        promotion defers and the caller's match ends at the resident
        prefix."""
        need = 1 + max(0, int(reserve))
        if len(self._free) < need:
            # The walked chain's nodes are pinned (refcount >= 2), so
            # displacement can never take a block this lookup relies on;
            # the node being promoted is freshly stamped and shielded
            # (_promoting) so a host-full displacement can't destroy it.
            node.last_used = self.radix._tick()
            self._promoting = node
            try:
                self.radix.evict(need - len(self._free))
            finally:
                self._promoting = None
        if len(self._free) < need:
            return False
        if self._promote_exe is None:
            def write_pair(caches, hk, hv, dst):
                return KVCache(
                    jax.lax.dynamic_update_slice_in_dim(
                        caches.k, hk[None].swapaxes(0, 1), dst, axis=1),
                    jax.lax.dynamic_update_slice_in_dim(
                        caches.v, hv[None].swapaxes(0, 1), dst, axis=1))

            if self.quantized:
                # Swap-in writes int8 payload AND scales verbatim — the
                # promoted block is bit-identical to what was demoted.
                def promote_block(caches, scales, hk, hv, hks, hvs, dst):
                    return (write_pair(caches, hk, hv, dst),
                            write_pair(scales, hks, hvs, dst))

                self._promote_exe = jax.jit(promote_block,
                                            donate_argnums=(0, 1))
            else:
                self._promote_exe = jax.jit(write_pair, donate_argnums=(0,))
        bid = self._free.pop()
        self._ref[bid] = 1  # the tree's own reference
        host = [self._host_k[node.host_slot], self._host_v[node.host_slot]]
        if self.quantized:
            host += [self._host_ks[node.host_slot],
                     self._host_vs[node.host_slot]]
        host = [jnp.asarray(h) for h in host]
        if self._device is not None:
            host = [jax.device_put(h, self._device) for h in host]
        if self.quantized:
            self.caches, self.scales = self._promote_exe(
                self.caches, self.scales, *host, jnp.int32(bid))
        else:
            self.caches = self._promote_exe(self.caches, *host,
                                            jnp.int32(bid))
        self._host_free.append(node.host_slot)
        node.host_slot = -1
        node.block_id = bid
        self.swap_ins += 1
        self.swapped_in_tokens += self.block_size
        return True

    # -- chain export/import (live stream migration; hold self.lock) ----------
    #
    # The wire format open item 4 needs ("page tables + block pool —
    # serialize blocks over the wire"): one JSON-safe dict per row chain,
    # dtype-preserving payload bytes per block (bf16 verbatim; quantized
    # pools ship int8 payload + the f32 scale vectors verbatim, so the
    # write-once rule survives the wire — an imported block is
    # bit-identical to the exported one, never requantized), a crc32
    # checksum over every payload byte in chain order, and the source
    # pool's generation stamp. DESIGN.md "Live stream migration".

    def _export_device_arrays(self, bids: Sequence[int]) -> List[np.ndarray]:
        """Device blocks ``bids`` -> host arrays [k, v(, ks, vs)], each
        shaped (L, n, ...) — ONE gather + transfer per tensor, not one
        per block: export runs on the decode thread under the pool
        lock, and a long chain must not stall every other live row for
        2·n (4·n quantized) round trips. The reads order after every
        donation that produced the blocks' bytes (same-lock rule)."""
        ids = jnp.asarray(np.asarray(bids, np.int32))
        out = [np.asarray(jax.device_get(self.caches.k[:, ids])),
               np.asarray(jax.device_get(self.caches.v[:, ids]))]
        if self.quantized:
            out += [np.asarray(jax.device_get(self.scales.k[:, ids])),
                    np.asarray(jax.device_get(self.scales.v[:, ids]))]
        return out

    def _export_host_arrays(self, slot: int) -> List[np.ndarray]:
        """A DEMOTED node's block, straight from its pinned host buffers
        — no swap-in, no device traffic (the demoted copy is bit-exact
        by the host-tier contract)."""
        out = [np.array(self._host_k[slot]), np.array(self._host_v[slot])]
        if self.quantized:
            out += [np.array(self._host_ks[slot]),
                    np.array(self._host_vs[slot])]
        return out

    def export_chain(self, sources: Sequence,
                     trace: Optional[dict] = None) -> dict:
        """Serialize a block chain. Each source is a device block id
        (int) or a ``_RadixNode`` (demoted nodes export from the host
        tier; resident ones from their device block). Returns the
        JSON-safe wire dict; ``import_chain`` on any same-geometry pool
        reproduces the exact bytes (tested bit-exact for bf16, int8 +
        scale, and host-demoted chains).

        ``trace``: optional trace-context header (cross-lane trace
        stitching, DESIGN.md "Observability plane") carried as a gated
        additive ``"trace"`` key — pure telemetry. Import-side
        validation (``chain_compatible``/``verify_chain``) checks named
        keys and block payloads only, so traced chains import into
        un-stitched lanes (and vice versa) unchanged; ``None`` (the
        default) keeps the wire dict byte-identical to today."""
        # Resolve each source to (device block id | host slot), then read
        # ALL device blocks in one batched gather+transfer per tensor.
        resolved = []
        dev_ids: List[int] = []
        for src in sources:
            if isinstance(src, _RadixNode) and src.demoted:
                resolved.append(("host", src.host_slot))
            else:
                bid = src.block_id if isinstance(src, _RadixNode) \
                    else int(src)
                resolved.append(("dev", len(dev_ids)))
                dev_ids.append(bid)
        dev = self._export_device_arrays(dev_ids) if dev_ids else None
        blocks = []
        crc = 0
        for kind, idx in resolved:
            if kind == "host":
                arrays = self._export_host_arrays(idx)
            else:
                arrays = [a[:, idx] for a in dev]
            entry = {}
            for name, arr in zip(("k", "v", "ks", "vs"), arrays):
                raw = arr.tobytes()
                crc = zlib.crc32(raw, crc)
                entry[name] = base64.b64encode(raw).decode("ascii")
            blocks.append(entry)
        out = {
            "version": 1,
            "dtype": str(jnp.dtype(self._dtype)),
            "quantized": self.quantized,
            "block_size": self.block_size,
            "n_layers": self.cfg.n_layers,
            "kv_heads": self.cfg.kv_heads,
            "d_head": self.cfg.d_head,
            "blocks": blocks,
            "checksum": crc,
            "generation": self.generation,
        }
        if self.tp > 1:
            # Shard-geometry stamp (gated: absent = 1, so pre-TP chains
            # and TP=1 lanes keep today's wire bytes). KV written under
            # different SPMD partitionings differs in low-order bits, so
            # a cross-degree import would resume a stream on bytes its
            # destination could never have produced — refused BY NAME
            # (chain_compatible), and the caller's replay fallback
            # recomputes instead.
            out["tp"] = self.tp
        if trace:
            out["trace"] = dict(trace)
        return out

    def chain_compatible(self, chain: dict) -> Optional[str]:
        """None when ``chain`` can be imported into THIS pool verbatim;
        else a human-readable reason. Geometry AND storage dtype must
        match exactly — a cross-dtype import would have to requantize,
        which the write-once rule forbids. Also validates every entry's
        STRUCTURE (required keys, exact decoded payload lengths): a
        chain whose checksum is self-consistent over truncated bytes
        must be refused HERE, on the import's validation path — never
        crash the decode thread mid-admission (a decode-thread failure
        recovers the whole pool and kills every live row on the lane)."""
        fam = chain.get("family")
        if fam not in (None, "kv_paged"):
            # Cross-family chains refuse by NAME, not by accidental
            # geometry mismatch: a state_slab chain holds a recurrent
            # state row, never KV blocks (and PR 11 kv chains predate
            # the key, so absent = kv_paged).
            return (f"chain family={fam!r} does not match destination "
                    f"pool family 'kv_paged'")
        want = {"dtype": str(jnp.dtype(self._dtype)),
                "quantized": self.quantized,
                "block_size": self.block_size,
                "n_layers": self.cfg.n_layers,
                "kv_heads": self.cfg.kv_heads,
                "d_head": self.cfg.d_head}
        for key, val in want.items():
            if chain.get(key) != val:
                return (f"chain {key}={chain.get(key)!r} does not match "
                        f"destination pool {key}={val!r}")
        try:
            chain_tp = int(chain.get("tp", 1))
        except (TypeError, ValueError):
            return f"chain tp={chain.get('tp')!r} is not an integer"
        if chain_tp != self.tp:
            # Mismatched shard geometry refuses BY NAME (never by an
            # accidental byte mismatch): KV computed under a different
            # tensor-parallel partitioning is not this lane's stream
            # history bit-for-bit — the replay resume recomputes it.
            return (f"chain tp={chain_tp} does not match destination "
                    f"pool tp={self.tp} (tensor-parallel shard "
                    f"geometry)")
        slots = self.cfg.n_layers * self.block_size * self.cfg.kv_heads
        payload_len = slots * self.cfg.d_head \
            * jnp.zeros((), self._dtype).dtype.itemsize
        want_lens = {"k": payload_len, "v": payload_len}
        if self.quantized:
            want_lens.update({"ks": slots * 4, "vs": slots * 4})
        blocks = chain.get("blocks")
        if not isinstance(blocks, (list, tuple)):
            return "chain carries no block list"
        for i, entry in enumerate(blocks):
            if not isinstance(entry, dict):
                return f"chain block {i} is not an object"
            for name, want_len in want_lens.items():
                raw = entry.get(name)
                if not isinstance(raw, str):
                    return f"chain block {i} is missing {name!r}"
                try:
                    n = len(base64.b64decode(raw, validate=True))
                except Exception:
                    return f"chain block {i} {name!r} is not base64"
                if n != want_len:
                    return (f"chain block {i} {name!r} holds {n} bytes, "
                            f"expected {want_len}")
        return None

    @staticmethod
    def verify_chain(chain: dict) -> bool:
        """Recompute the chain checksum over the decoded payload bytes —
        the destination's first gate, BEFORE any block is allocated.
        Structurally garbage chains (blocks not a list of objects) are
        False, never a pass-through: an empty or non-iterable block
        list must not verify against a zero checksum."""
        crc = 0
        try:
            blocks = chain["blocks"]
            if not isinstance(blocks, (list, tuple)):
                return False
            for entry in blocks:
                if not isinstance(entry, dict):
                    return False
                for name in ("k", "v", "ks", "vs"):
                    if name in entry:
                        crc = zlib.crc32(
                            base64.b64decode(entry[name]), crc)
            return crc == int(chain["checksum"])
        except Exception:
            return False

    def _chain_block_arrays(self, chain: dict, entry: dict):
        """One wire block -> host arrays shaped for a device write: the
        wire's (L, bs, H_kv, D) payload bytes ARE the pool's
        (L, bs, H_kv*D) block, in order."""
        cfg = self.cfg
        slots = (cfg.n_layers, self.block_size)
        dt = jnp.zeros((), self._dtype).dtype
        out = [np.frombuffer(base64.b64decode(entry[name]), dtype=dt)
               .reshape(slots + (cfg.kv_heads * cfg.d_head,))
               for name in ("k", "v")]
        if self.quantized:
            out += [np.frombuffer(base64.b64decode(entry[name]),
                                  dtype=np.float32)
                    .reshape(slots + (cfg.kv_heads,))
                    for name in ("ks", "vs")]
        return out

    def import_chain(self, chain: dict, entries: Sequence[dict],
                     ids: Sequence[int]) -> None:
        """Write wire blocks ``entries`` into already-allocated device
        blocks ``ids`` VERBATIM (one jitted batched write, donating the
        pool like every other pool-writing dispatch). int8 payloads and
        scale vectors land untouched — the one rule that keeps a
        migrated quantized stream deterministic. Caller holds the lock
        and has verified checksum + compatibility."""
        if not ids:
            return
        n = len(ids)
        if self._import_exe.get(n) is None:
            if self.quantized:
                def write_n(caches, scales, ks, vs, kss, vss, dst):
                    return (KVCache(caches.k.at[:, dst].set(ks),
                                    caches.v.at[:, dst].set(vs)),
                            KVCache(scales.k.at[:, dst].set(kss),
                                    scales.v.at[:, dst].set(vss)))

                self._import_exe[n] = jax.jit(write_n,
                                              donate_argnums=(0, 1))
            else:
                def write_n(caches, ks, vs, dst):
                    return KVCache(caches.k.at[:, dst].set(ks),
                                   caches.v.at[:, dst].set(vs))

                self._import_exe[n] = jax.jit(write_n, donate_argnums=(0,))
        per = [self._chain_block_arrays(chain, e) for e in entries]
        # (n, L, bs, H*D) -> (L, n, bs, H*D): the pool's block axis.
        stacked = [np.stack([p[i] for p in per]).swapaxes(0, 1)
                   for i in range(len(per[0]))]
        host = [jnp.asarray(a) for a in stacked]
        if self._device is not None:
            host = [jax.device_put(a, self._device) for a in host]
        dst = jnp.asarray(np.asarray(ids, np.int32))
        if self.quantized:
            self.caches, self.scales = self._import_exe[n](
                self.caches, self.scales, *host, dst)
        else:
            self.caches = self._import_exe[n](self.caches, *host, dst)

    def reset(self) -> None:
        """Post-device-failure recovery: the donated pool buffers may be
        invalid — rebuild everything (mirrors the dense scheduler's
        `_recover`). The host tier empties too: its blocks are only
        meaningful as radix entries, and the tree died with the pool —
        pins and page tables taken against the old generation are void
        (holders compare ``generation``, never release stale ids)."""
        self.generation += 1
        self.caches = self._init_device()
        self._ref[:] = 0
        self._ref[0] = 1
        self._free = list(range(self.num_blocks - 1, 0, -1))
        self.radix = RadixTree(self)
        if self.host_blocks > 0:
            self._host_free = list(range(self.host_blocks - 1, -1, -1))

    def bytes_per_block(self) -> int:
        """HBM bytes ONE block costs in this pool's layout: K+V payload
        at the storage dtype, plus (quantized) the per-slot f32 scales."""
        if self.quantized:
            return quant_block_bytes(self.cfg, self.block_size)
        return dense_block_bytes(self.cfg, self.block_size, self._dtype)

    def dense_bytes_per_block(self) -> int:
        """What the SAME block would cost unquantized (at io_dtype) — the
        equal-byte-budget baseline `capacity_multiplier` is quoted against."""
        return dense_block_bytes(self.cfg, self.block_size, self.io_dtype)

    def _demoted_nodes(self) -> int:
        """Radix nodes currently holding a host slot (caller holds the
        lock) — the pairing side of the host scale-slot leak check."""
        n, stack = 0, [self.radix.root]
        while stack:
            node = stack.pop()
            for c in node.children.values():
                stack.append(c)
                n += int(c.demoted)
        return n

    def stats(self) -> dict:
        with self.lock:
            shared = int(np.sum(self._ref[1:] > 1))
            hit, filled = self.prefix_hit_tokens, self.prefilled_tokens
            out = {
                "blocks_total": self.num_blocks - 1,  # null excluded
                "block_size": self.block_size,
                "blocks_free": len(self._free),
                "blocks_shared": shared,
                "radix_nodes": self.radix.nodes,
                "evictions": self.evictions,
                "cow_copies": self.cow_copies,
                "prefix_hit_tokens": hit,
                "prefilled_tokens": filled,
                "prefix_savings_frac": round(hit / (hit + filled), 4)
                if hit + filled else 0.0,
                # Per-lane radix effectiveness (the affinity bench's and
                # the gateway /stats blind-spot fix's raw numbers).
                "radix_lookups": self.radix_lookups,
                "radix_hits": self.radix_hits,
            }
            if self.tp > 1:
                # Additive, present ONLY in tensor-parallel pools
                # (defaults-off /stats and /health bytes identical):
                # the shard geometry plus the per-DEVICE block cost —
                # the number the equal-per-device-HBM A/B provisions by.
                out["tp"] = self.tp
                out["bytes_per_block_per_device"] = (
                    self.bytes_per_block() // self.tp)
            if self.quantized:
                # Additive, present ONLY in quantized pools (defaults-off
                # /stats and /health bytes stay byte-identical).
                bpb = self.bytes_per_block()
                dense = self.dense_bytes_per_block()
                out["quantized"] = "int8"
                out["bytes_per_block"] = bpb
                out["dense_bytes_per_block"] = dense
                out["capacity_multiplier"] = round(dense / bpb, 3)
            if self.host_blocks > 0:
                used = self.host_blocks - len(self._host_free)
                out["host"] = {
                    "blocks_total": self.host_blocks,
                    "blocks_used": used,
                    "demotions": self.demotions,
                    "swap_ins": self.swap_ins,
                    "swap_in_events": self.swap_in_events,
                    "swap_in_deferred": self.swap_in_deferred,
                    "host_evictions": self.host_evictions,
                    "swapped_in_tokens": self.swapped_in_tokens,
                }
                if self.quantized:
                    # Scale slots pair 1:1 with payload slots: a slot
                    # counted used with no demoted node referencing it
                    # (or vice versa) is a leak — fault_injection --quant
                    # asserts this stays 0 across kill -9 survivors.
                    out["host"]["scale_slots_used"] = used
                    out["host"]["scale_slots_leaked"] = (
                        used - self._demoted_nodes())
            return out


class StateSlabPool:
    """Fixed-size recurrent-state rows for the ``state_slab`` model
    family (SSD/Mamba — models.ssd): one ``(n_layers, state_dim)`` f32
    row per live stream, CONSTANT in sequence length. The paged pool's
    "KV capacity" becomes "state capacity" here: a row costs the same
    HBM at token 1 and token 100k, so peak concurrent rows are
    independent of stream length (tests/test_ssd.py holds the count).

    Same host-side discipline as ``BlockPool`` — one lock over the free
    list/refcounts that ALSO orders pool-touching device dispatches
    (the decode tick donates ``slab``; admission writes and chain
    exports order against it under the lock), a reserved null row 0 for
    free slots' gather/scatter targets, and a generation stamp that
    voids row ids across ``reset()`` rebuilds.

    Deliberately NO radix tree and no prefix sharing: a recurrent
    prefix is a dense nonlinear state, not a block-addressable chain —
    two prompts sharing a prefix produce states that cannot be split,
    shared, or partially matched. ``stats()`` says so loudly
    (``prefix_sharing: "unsupported: recurrent state is not
    block-addressable"``) so operators never hunt for a radix knob
    that cannot exist for this family.

    Chain wire format: a state row serializes as a ONE-pseudo-block
    chain over the PR 11 ``export_chain`` shape — a ``blocks`` list
    with a single ``{"k": <payload b64>}`` entry, a crc32 checksum, and
    the pool generation — so ``BlockPool.verify_chain`` verifies it
    unchanged and drain/migration/handoff machinery (gateway,
    /admin/migrate, ``migrate_import``) composes for free."""

    def __init__(self, n_layers: int, state_dim: int, num_rows: int,
                 dtype=jnp.float32, device=None):
        if num_rows < 2:
            raise ValueError("need >= 2 state rows (row 0 is the null row)")
        self.n_layers = int(n_layers)
        self.state_dim = int(state_dim)
        self.num_rows = int(num_rows)
        self._dtype = dtype
        self._device = device
        # One lock for bookkeeping AND slab-touching dispatch ordering
        # (BlockPool's rule). RLock for symmetry with BlockPool — stats
        # helpers may nest.
        self.lock = threading.RLock()
        self.generation = 0
        self.slab = self._init_device()
        self._ref = np.zeros((self.num_rows,), np.int32)
        self._ref[0] = 1  # null row: permanently pinned, never allocated
        self._free: List[int] = list(range(self.num_rows - 1, 0, -1))
        self._import_exe = None
        # Counters for the gated /stats `state_pool` block and the
        # `tpu_engine_state_*` metrics family.
        self.rows_admitted = 0
        self.rows_released = 0
        self.exports = 0
        self.imports = 0

    def _init_device(self):
        slab = jnp.zeros((self.n_layers, self.num_rows, self.state_dim),
                         self._dtype)
        if self._device is not None:
            slab = jax.device_put(slab, self._device)
        return slab

    # -- bookkeeping (hold self.lock) -----------------------------------------

    @property
    def rows_free(self) -> int:
        return len(self._free)

    def refcount(self, row_id: int) -> int:
        return int(self._ref[row_id])

    def alloc_row(self) -> int:
        """One fresh state row (refcount 1). Raises PoolExhausted (state
        unchanged) when none is free — the scheduler defers the
        admission exactly like a paged pool under block pressure."""
        if not self._free:
            raise PoolExhausted(
                f"no free state rows ({self.num_rows - 1} total)")
        rid = self._free.pop()
        self._ref[rid] = 1
        self.rows_admitted += 1
        return rid

    def release_row(self, row_id: int) -> None:
        if row_id == 0:
            return  # null row: permanent
        self._ref[row_id] -= 1
        assert self._ref[row_id] >= 0, "double free of a state row"
        if self._ref[row_id] == 0:
            self._free.append(row_id)
            self.rows_released += 1

    # -- chain export/import (one-pseudo-block wire format) -------------------

    def export_row_chain(self, row_id: int) -> dict:
        """Serialize one state row as a one-pseudo-block chain. The
        device read orders after every donation that produced the row's
        bytes (same-lock rule); the payload is verbatim f32 bytes, so
        an import on any same-geometry pool is bit-exact (tested)."""
        raw = np.asarray(
            jax.device_get(self.slab[:, row_id])).tobytes()
        self.exports += 1
        return {
            "version": 1,
            "family": "state_slab",
            "dtype": str(jnp.dtype(self._dtype)),
            "n_layers": self.n_layers,
            "state_dim": self.state_dim,
            "blocks": [{"k": base64.b64encode(raw).decode("ascii")}],
            "checksum": zlib.crc32(raw),
            "generation": self.generation,
        }

    def chain_compatible(self, chain: dict) -> Optional[str]:
        """None when ``chain`` can be imported into THIS pool verbatim;
        else a human-readable refusal. Family, geometry, and dtype must
        match exactly, and the single pseudo-block's decoded payload
        must hold exactly one row's bytes — refused HERE, before any
        row is allocated (BlockPool.chain_compatible's contract)."""
        want = {"family": "state_slab",
                "dtype": str(jnp.dtype(self._dtype)),
                "n_layers": self.n_layers,
                "state_dim": self.state_dim}
        for key, val in want.items():
            if chain.get(key) != val:
                return (f"chain {key}={chain.get(key)!r} does not match "
                        f"destination state pool {key}={val!r}")
        blocks = chain.get("blocks")
        if not isinstance(blocks, (list, tuple)) or len(blocks) != 1:
            return "state chain must carry exactly one pseudo-block"
        entry = blocks[0]
        if not isinstance(entry, dict) or not isinstance(entry.get("k"),
                                                         str):
            return "state chain block 0 is missing its payload"
        try:
            n = len(base64.b64decode(entry["k"], validate=True))
        except Exception:
            return "state chain block 0 payload is not base64"
        want_len = (self.n_layers * self.state_dim
                    * jnp.zeros((), self._dtype).dtype.itemsize)
        if n != want_len:
            return (f"state chain block 0 holds {n} bytes, expected "
                    f"{want_len}")
        return None

    # The checksum gate is byte-shape-agnostic: the paged pool's
    # verifier works on the one-pseudo-block chain unchanged.
    verify_chain = staticmethod(BlockPool.verify_chain)

    def import_row_chain(self, chain: dict, row_id: int) -> None:
        """Write a verified chain's payload into an already-allocated
        row VERBATIM (one jitted donating write, like every other
        slab-writing dispatch). Caller holds the lock and has run
        chain_compatible + verify_chain."""
        if self._import_exe is None:
            def write_row(slab, flat, rid):
                return slab.at[:, rid].set(flat)

            self._import_exe = jax.jit(write_row, donate_argnums=(0,))
        dt = jnp.zeros((), self._dtype).dtype
        flat = np.frombuffer(
            base64.b64decode(chain["blocks"][0]["k"]),
            dtype=dt).reshape(self.n_layers, self.state_dim)
        host = jnp.asarray(flat)
        if self._device is not None:
            host = jax.device_put(host, self._device)
        self.slab = self._import_exe(self.slab, host, jnp.int32(row_id))
        self.imports += 1

    def reset(self) -> None:
        """Post-device-failure recovery (BlockPool.reset's contract):
        the donated slab may be invalid — rebuild it, void every row id
        issued against the old generation."""
        self.generation += 1
        self.slab = self._init_device()
        self._ref[:] = 0
        self._ref[0] = 1
        self._free = list(range(self.num_rows - 1, 0, -1))

    def bytes_per_row(self) -> int:
        """HBM bytes ONE stream's whole autoregressive state costs —
        constant in sequence length (the family's capacity story; an
        equal-HBM comparison with a paged pool sizes both with this and
        dense_block_bytes, never a re-derivation)."""
        return int(self.n_layers * self.state_dim
                   * jnp.zeros((), self._dtype).dtype.itemsize)

    def stats(self) -> dict:
        with self.lock:
            return {
                "rows_total": self.num_rows - 1,  # null row excluded
                "rows_free": len(self._free),
                "state_dim": self.state_dim,
                "n_layers": self.n_layers,
                "bytes_per_row": self.bytes_per_row(),
                "rows_admitted": self.rows_admitted,
                "rows_released": self.rows_released,
                "exports": self.exports,
                "imports": self.imports,
                # Loud, structural, and deliberate — not a missing
                # feature: a recurrent prefix is a dense nonlinear
                # state, never a block-addressable chain, so there is
                # no radix tree, no COW, no prefix skip for this
                # family (DESIGN.md "Recurrent state serving").
                "prefix_sharing":
                    "unsupported: recurrent state is not "
                    "block-addressable",
            }


class StateRowPool:
    """The recurrent mixers' rows of the ``kv_and_state`` family, where a
    stream ALSO holds a block chain, of K and V a head
    (models.olmo_hybrid, models.falcon_h1) or of latents
    (models.kimi_linear): a row is, for each of the `n_layers` layers that
    have a recurrent mixer (some of the model's, or every one where a
    layer has both mixers), one float32 array of each of `shapes` (the
    state and the conv tail), kept as arrays of their own shape
    ``(n_layers, n_slots + 1, *shape)`` so that the step reads and writes
    them where they lie. `slab` is the tuple of them, donated through the
    tick like the block pool's pair.

    Scheduler slot ``s`` owns row ``s + 1`` for as long as it serves a
    request, and row 0 is the null row a free slot's writes land in: a row
    is held only by a slot, so there is no free list, nothing to run
    short of and nothing to lock (the tick thread alone takes, gives and
    dispatches). `rows` is the vector the step indexes by: a slot's row,
    0 while it is free. A row is not cleared when it is given back: the
    step starts a request's state from zero at position 0. No chain is
    exported (the family declares no migration)."""

    def __init__(self, n_layers: int, shapes, n_slots: int, device=None):
        self.n_layers = int(n_layers)
        self.shapes = tuple(tuple(int(n) for n in shape) for shape in shapes)
        self.num_rows = int(n_slots) + 1
        self._device = device
        self.rows = np.zeros((int(n_slots),), np.int32)
        self.rows_peak = 0
        self.slab = self._init_device()

    def _init_device(self):
        slab = tuple(jnp.zeros((self.n_layers, self.num_rows) + shape,
                               jnp.float32) for shape in self.shapes)
        if self._device is not None:
            slab = jax.device_put(slab, self._device)
        return slab

    @property
    def rows_held(self) -> int:
        return int(np.count_nonzero(self.rows))

    def take(self, slot: int) -> None:
        self.rows[slot] = slot + 1
        self.rows_peak = max(self.rows_peak, self.rows_held)

    def give(self, slot: int) -> None:
        self.rows[slot] = 0

    def reset(self) -> None:
        """After a device failure the donated arrays may be invalid:
        rebuilt, and no slot holds a row."""
        self.slab = self._init_device()
        self.rows[:] = 0

    def bytes_per_row(self) -> int:
        return 4 * self.n_layers * sum(int(np.prod(shape))
                                       for shape in self.shapes)

    def stats(self) -> dict:
        held = self.rows_held
        return {"rows_total": self.num_rows - 1,  # null row excluded
                "rows_free": self.num_rows - 1 - held,
                "rows_held": held,
                "rows_peak": self.rows_peak,
                "n_layers": self.n_layers,
                "bytes_per_row": self.bytes_per_row()}


# -- device-side block movement -------------------------------------------------
#
# A whole row cache written into pool blocks. No served path calls these
# since the ragged tick writes a row's tokens where they lie (PR 49); the
# pool's tests fill blocks with known bytes through them.

def scatter_blocks(caches, row_k, row_v, ids):
    """Write a (L, 1, nb*bs, H, D) row cache into pool blocks ``ids``.
    Entries mapped to 0 dump into the null block. Donate `caches`."""
    L, _, bs, hd = caches.k.shape
    blocks = (L, ids.shape[0], bs, hd)
    rk = row_k.reshape(blocks).astype(caches.k.dtype)
    rv = row_v.reshape(blocks).astype(caches.v.dtype)
    return KVCache(caches.k.at[:, ids].set(rk), caches.v.at[:, ids].set(rv))


def scatter_blocks_quant(caches, scales, row_k, row_v, ids):
    """`scatter_blocks` for the int8 pool: quantize the row cache ONCE —
    one symmetric int8 vector + f32 scale per (layer, slot, kv-head) —
    and write payload and scales together. Donate `caches` AND
    `scales`."""
    from tpu_engine.ops.quant import quantize_kv

    L, _, bs, h = scales.k.shape
    blocks = (L, ids.shape[0], bs)
    qk, sk = quantize_kv(row_k.reshape(blocks + (h, -1)))
    qv, sv = quantize_kv(row_v.reshape(blocks + (h, -1)))
    return (KVCache(caches.k.at[:, ids].set(qk.reshape(blocks + (-1,))),
                    caches.v.at[:, ids].set(qv.reshape(blocks + (-1,)))),
            KVCache(scales.k.at[:, ids].set(sk),
                    scales.v.at[:, ids].set(sv)))
