"""paddle_tpu.inference.kv_cache — block-paged KV-cache pool for serving.

One-shot static generation (generate_static_ragged) right-pads every
ragged prompt to a fixed cap and reserves a full [B, max_len] KV slab per
batch row, so mixed-length traffic would hold HBM hostage for padding and a
finished row's slab would stay pinned until the whole batch ends. The
serving engine's KV (Ragged Paged Attention, arxiv 2604.15464; PAPERS.md
serving studies) is a BLOCK pool:

  * device state is ONE fixed-shape tensor per layer and plane —
    ``[num_blocks, block_size, num_heads, head_dim]`` K and V planes, or
    whatever one block of the model's cache is (`block_shapes`: a latent-
    attention model pools one ``[num_blocks, latent_width, block_size]``
    plane a layer) — plus an int32 block
    table ``[B, max_blocks]`` and a length vector ``[B]``. Every shape is
    pinned, so a single compiled executable serves ANY mix of request
    lengths (the whole point: zero steady-state recompiles);
  * a request owns ``ceil(tokens / block_size)`` blocks, scattered anywhere
    in the pool — blocks free the moment the request finishes, and a queued
    request is spliced into the vacated batch slot mid-flight.

``BlockPool`` is the HOST-side allocator: free-list bookkeeping, per-owner
block lists, occupancy accounting. The device pool arrays it creates are
handed to the caller (ServingEngine / prefill_paged), which threads them
through jitted steps with the buffers DONATED — XLA updates the pool in
place instead of round-tripping a copy.

Block 0 is reserved as the TRASH block: block-table padding entries and
masked writes (right-padded prompt garbage, post-EOS decode steps of a
fixed-shape chunk) all land there, so scatter updates never need a mask and
can never corrupt another request's blocks. Usable capacity is therefore
``(num_blocks - 1) * block_size`` tokens.

Blocks are REFCOUNTED (ISSUE 10): the prefix cache maps one physical
block into many requests' tables (``alloc(..., shared=...)``) and holds
its own reference on cached blocks (:meth:`retain`); a block returns to
the free list only when its last reference drops (:meth:`free` /
:meth:`release`). The trash block is never issued, never shared, never
counted. ``cache_dtype="int8"`` pools carry int8 code payloads plus
per-(block-row, head) f32 factored scales — same quantization scheme as
the static int8 KV path (ops.attention.quantize_kv), so the pool holds
~2x the resident tokens for the same HBM.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional

import numpy as np


class BlockPool:
    """Fixed-size KV block allocator (host bookkeeping + device pools).

    Parameters
    ----------
    num_blocks : total blocks in the pool, INCLUDING the reserved trash
        block 0 (usable capacity is ``(num_blocks - 1) * block_size``).
    block_size : KV rows (token positions) per block.
    num_layers / block_shapes / head_axis / dtype : pool tensor geometry —
        normally taken from the model via :meth:`for_model`.
        `block_shapes` is the shape of ONE block of each plane a layer
        pools: K and V, each ``(block_size, num_heads, head_dim)``, for
        GPT; one ``(latent_width, block_size)`` for a latent-attention
        model. `head_axis` is where a block has its heads (1 for K and V;
        None: no head axis, so nothing to shard over mp and no per-(row,
        head) scales). The allocator, reference counts, copy-on-write,
        spill payloads and the prefix trie move whole blocks and never
        look inside one.
    cache_dtype : None = pools carry the model dtype; "int8" = every
        plane is a (codes int8, scale f32) pair with per-(row, head)
        factored scales (the static int8-KV trick ported to the paged
        pool); needs a head axis.
    layer_block_shapes / state_shapes / state_rows / snapshot_rows : a
        model whose layers do not all cache the same thing states its
        planes layer by layer. `layer_block_shapes[i]` are the paged
        planes of layer i (none for a layer that keeps no pages);
        `state_shapes[i]` are its RECURRENT STATE arrays, float32, one
        row a batch slot and not a page a block: the pool holds each as
        ``[state_rows, *shape]`` for the engine's slots and
        ``[snapshot_rows, *shape]`` for the snapshots the prefix trie
        keeps where a cached prefix ends (`state_move` zeroes, saves and
        restores a row). One manager, one memory account:
        `bytes_per_block` is a block's over the layers that page,
        `state_bytes` what the state planes pin.
    """

    def __init__(self, *, num_blocks: int, block_size: int,
                 num_layers: int, block_shapes=None, head_axis: int = None,
                 dtype="float32", cache_dtype=None, layer_block_shapes=None,
                 state_shapes=None, state_rows: int = 0,
                 snapshot_rows: int = 0):
        if num_blocks < 2:
            raise ValueError("num_blocks must be >= 2 (block 0 is the "
                             "reserved trash block)")
        if block_size < 1:
            raise ValueError("block_size must be >= 1")
        if cache_dtype not in (None, "int8"):
            raise ValueError(f"cache_dtype must be None or 'int8'; "
                             f"got {cache_dtype!r}")
        if cache_dtype is not None and head_axis is None:
            raise ValueError("cache_dtype='int8' scales per (row, head): "
                             "it needs planes with a head axis")
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.num_layers = int(num_layers)
        # the planes each layer pages: the same for every layer
        # (`block_shapes`), or stated layer by layer, some with none
        if layer_block_shapes is None:
            layer_block_shapes = [block_shapes] * self.num_layers
        self.layer_block_shapes = tuple(
            tuple(tuple(int(d) for d in shp) for shp in layer)
            for layer in layer_block_shapes)
        self.block_shapes = self.layer_block_shapes[0]
        # the second kind of plane: a recurrent state a ROW, not a page.
        # `state_shapes[i]` are layer i's state arrays (float32); each is
        # held as [state_rows, *shape] for the live slots plus
        # [snapshot_rows, *shape] for the prefix trie's snapshots
        self.state_shapes = tuple(
            tuple(tuple(int(d) for d in shp) for shp in layer)
            for layer in (state_shapes or [()] * self.num_layers))
        self.has_state = any(self.state_shapes)
        self.state_rows = int(state_rows) if self.has_state else 0
        self.snapshot_rows = int(snapshot_rows) if self.has_state else 0
        if self.has_state and (cache_dtype is not None
                               or self.state_rows < 1
                               or self.snapshot_rows < 1):
            raise ValueError("state planes need state_rows and "
                             "snapshot_rows >= 1 and no cache_dtype")
        self.head_axis = head_axis
        # heads of a plane (None: the planes have no head axis)
        self.num_heads = None if head_axis is None \
            else self.block_shapes[0][head_axis]
        self.dtype = dtype
        self.cache_dtype = cache_dtype
        # LIFO free list: recently freed blocks are re-issued first, which
        # keeps the hot working set of pool pages small
        self._free: List[int] = list(range(self.num_blocks - 1, 0, -1))
        self._rows: Dict[int, List[int]] = {}
        self._refs: Dict[int, int] = {}     # block id -> reference count
        # observer poked after every occupancy change (alloc/free/take/
        # release/reset) — the MemoryLedger's per-owner delta stream rides
        # this; must stay host-side and cheap, it sits on the alloc path
        self.on_change = None

    @classmethod
    def for_model(cls, model, *, num_blocks: int, block_size: int,
                  cache_dtype=None, state_rows: int = 0,
                  snapshot_rows: int = 0):
        """Geometry from the model's `kv_pool_geometry(block_size)`:
        num_layers, block_shapes (or layer_block_shapes and
        state_shapes), head_axis and dtype. `state_rows` (the engine's
        slots) and `snapshot_rows` size the state planes of a model that
        has them."""
        return cls(num_blocks=num_blocks, block_size=block_size,
                   cache_dtype=cache_dtype, state_rows=state_rows,
                   snapshot_rows=snapshot_rows,
                   **model.kv_pool_geometry(block_size))

    def make_pools(self):
        """Fresh zeroed device pools. Per layer one array a plane, each
        ``[num_blocks, *block_shape]``: ``(k_pool, v_pool)`` of
        ``[NB, bs, H, D]`` for GPT — or, for ``cache_dtype="int8"``, a
        (codes, scale) pair a plane, ``(k_codes, k_scale, v_codes,
        v_scale)`` with int8 ``[NB, bs, H, D]`` codes and f32
        ``[NB, bs, H]`` factored scales. The caller owns them from here —
        jitted steps donate and replace them, so the allocator
        deliberately does NOT keep a reference.

        Under an active mesh with an ``mp`` axis (multi-chip serving,
        ISSUE 16) the pools come up HEAD-SHARDED: the head axis split
        over mp (int8 scale pools shard the same axis, so codes and
        their scales always live on the same shard). Block tables, the
        free list, refcounts, and every other allocator structure stay
        host-side and replicated — sharding is purely a device-placement
        property of the arrays."""
        import jax
        import jax.numpy as jnp
        from ..distributed import mesh as _mesh
        mp = _mesh.mesh_axis_size("mp")
        if mp > 1 and (self.num_heads is None or self.num_heads % mp != 0):
            raise ValueError(
                f"pools shard their head axis over mp: they need planes "
                f"with a head axis divisible by the mp axis; got "
                f"block_shapes={self.block_shapes}, head_axis="
                f"{self.head_axis}, mp={mp}")

        def _zeros(shape, dtype):
            z = jnp.zeros((self.num_blocks,) + shape, dtype)
            if self.head_axis is not None:
                spec = [None] * len(shape)
                spec[self.head_axis] = "mp"
                sh = _mesh.named_sharding(None, *spec)
                if sh is not None:
                    z = jax.device_put(z, sh)
            return z

        def _plane(shape):
            if self.cache_dtype == "int8":      # scale: per (row, head)
                return (_zeros(shape, jnp.int8),
                        _zeros(shape[:-1], jnp.float32))
            return (_zeros(shape, self.dtype),)

        def _state(shape):          # (the slots' rows, the snapshots')
            return tuple(jnp.zeros((n,) + shape, jnp.float32)
                         for n in (self.state_rows, self.snapshot_rows))
        return [sum((_plane(shp) for shp in paged), ())
                + sum((_state(shp) for shp in state), ())
                for paged, state in zip(self.layer_block_shapes,
                                        self.state_shapes)]

    # ------------------------------------------------------------- sizing
    def blocks_needed(self, tokens: int) -> int:
        return max(0, math.ceil(int(tokens) / self.block_size))

    @property
    def capacity_blocks(self) -> int:
        """Allocatable blocks (trash block excluded)."""
        return self.num_blocks - 1

    @property
    def capacity_tokens(self) -> int:
        return self.capacity_blocks * self.block_size

    @property
    def bytes_per_block(self) -> int:
        """HBM bytes ONE block pins across every layer's planes — the
        unit the prefix cache's byte budget is charged in."""
        if self.cache_dtype == "int8":          # codes + f32 scale
            return sum(math.prod(shp) + 4 * math.prod(shp[:-1])
                       for layer in self.layer_block_shapes for shp in layer)
        return sum(math.prod(shp) for layer in self.layer_block_shapes
                   for shp in layer) * np.dtype(self.dtype).itemsize

    @property
    def state_bytes_per_row(self) -> int:
        """HBM bytes ONE row of the state planes pins across the layers
        that have them: a live slot's, or a snapshot's."""
        return 4 * sum(math.prod(shp) for layer in self.state_shapes
                       for shp in layer)

    @property
    def state_bytes(self) -> int:
        """What the state planes pin in all: slots and snapshots."""
        return self.state_bytes_per_row * (self.state_rows
                                           + self.snapshot_rows)

    def state_move(self, pools, op: int, slot: int, snap: int):
        """One row of every state plane zeroed (`STATE_ZERO`: the slot's),
        saved (`STATE_SAVE`: slot -> snapshot) or restored (`STATE_LOAD`:
        snapshot -> slot). The operation and both rows are DATA of one
        small donated executable. Returns the replaced pools."""
        import jax.numpy as jnp
        n_paged = tuple(len(layer) for layer in self.layer_block_shapes)
        sig = ("state_move", self.state_rows, self.snapshot_rows,
               self.state_shapes)
        fn = _SPILL_SCATTER_CACHE.get(sig)
        if fn is None:
            from ..jit.api import (STATE_MOVE_PROGRAM, _note_cache_miss,
                                   named_program)
            _note_cache_miss()

            def run(pools, op, slot, snap):
                out = []
                for layer, n in zip(pools, n_paged):
                    planes = list(layer)
                    for j in range(n, len(planes), 2):
                        rows, snaps = planes[j], planes[j + 1]
                        row, kept = rows[slot], snaps[snap]
                        planes[j] = rows.at[slot].set(jnp.where(
                            op == STATE_ZERO, 0.0,
                            jnp.where(op == STATE_LOAD, kept, row)))
                        planes[j + 1] = snaps.at[snap].set(
                            jnp.where(op == STATE_SAVE, row, kept))
                    out.append(tuple(planes))
                return out
            fn = _SPILL_SCATTER_CACHE[sig] = named_program(
                run, STATE_MOVE_PROGRAM, donate_argnums=(0,))
        return fn(pools, np.int32(op), np.int32(slot), np.int32(snap))

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def used_blocks(self) -> int:
        return self.capacity_blocks - len(self._free)

    def fits_ever(self, tokens: int) -> bool:
        """Could a request needing `tokens` KV rows EVER be served by this
        pool (i.e. with every other request drained)? False means reject —
        waiting in the queue would never help."""
        return self.blocks_needed(tokens) <= self.capacity_blocks

    # --------------------------------------------------------- alloc/free
    def alloc(self, owner: int, tokens: int,
              shared=None) -> Optional[np.ndarray]:
        """Reserve blocks covering `tokens` KV rows for `owner`.

        `shared` (prefix cache, ISSUE 10) maps already-populated blocks —
        in PREFIX ORDER — into the reservation instead of allocating
        fresh ones: each gains a reference, and only
        ``blocks_needed(tokens) - len(shared)`` fresh blocks come off the
        free list, appended after the shared run (so the returned vector
        is the request's block-table row in position order).

        Returns the block-id vector (int32) on success, None when the pool
        has too few FREE blocks right now (the caller decides whether to
        wait or reject — see `fits_ever` for the never-fits case). An owner
        can hold only one reservation; double-alloc raises."""
        if owner in self._rows:
            raise ValueError(f"owner {owner} already holds "
                             f"{len(self._rows[owner])} blocks; free first")
        shared = [int(b) for b in (shared or ())]
        if any(b == 0 for b in shared):
            raise ValueError("the trash block (0) is never shared")
        n = self.blocks_needed(tokens) - len(shared)
        if n < 0:
            raise ValueError(f"shared prefix ({len(shared)} blocks) longer "
                             f"than the reservation ({tokens} tokens)")
        if n > len(self._free):
            return None
        for b in shared:
            if self._refs.get(b, 0) < 1:
                raise ValueError(f"block {b} is not live; cannot share")
            self._refs[b] += 1
        fresh = [self._free.pop() for _ in range(n)]
        for b in fresh:
            self._refs[b] = 1
        blocks = shared + fresh
        self._rows[owner] = blocks
        self._notify()
        return np.asarray(blocks, dtype=np.int32)  # lint: allow(tracer-asarray)

    def free(self, owner: int) -> int:
        """Drop `owner`'s reference on every block it holds; returns how
        many actually RETURNED to the free list (a block another owner or
        the prefix cache still references stays resident). Freeing an
        unknown owner is a no-op (0) — finish paths may race a reject."""
        blocks = self._rows.pop(owner, None)
        if not blocks:
            return 0
        freed = self._deref(reversed(blocks))
        self._notify()
        return freed

    def take(self, n: int = 1) -> Optional[List[int]]:
        """Reserve `n` OWNERLESS blocks at refcount 1 — the rehydrate
        path's allocation (ISSUE 14): a spilled prefix block coming back
        from host RAM belongs to the cache, not to any request, exactly
        like a retained block whose computing owner already finished.
        Balanced by :meth:`release`. Returns the block ids, or None when
        the free list is short (the caller evicts/reclaims and retries
        or drops the rehydrate)."""
        if n < 1 or n > len(self._free):
            return None
        out = []
        for _ in range(n):
            b = self._free.pop()
            self._refs[b] = 1
            out.append(b)
        self._notify()
        return out

    # ------------------------------------------------- cache references
    def retain(self, blocks) -> None:
        """Add one reference per block — how the prefix cache pins a
        cached prefix independent of the request that computed it."""
        for b in blocks:
            b = int(b)
            if b == 0:
                raise ValueError("the trash block (0) is never retained")
            if self._refs.get(b, 0) < 1:
                raise ValueError(f"block {b} is not live; cannot retain")
            self._refs[b] += 1

    def release(self, blocks) -> int:
        """Drop one reference per block (cache eviction path); returns
        how many hit zero and went back to the free list."""
        freed = self._deref(int(b) for b in blocks)
        self._notify()
        return freed

    def refcount(self, block: int) -> int:
        return self._refs.get(int(block), 0)

    def _deref(self, blocks) -> int:
        freed = 0
        for b in blocks:
            b = int(b)
            r = self._refs.get(b, 0)
            if r < 1:
                raise ValueError(f"refcount underflow on block {b}")
            if r == 1:
                del self._refs[b]
                self._free.append(b)
                freed += 1
            else:
                self._refs[b] = r - 1
        return freed

    def owned(self, owner: int) -> List[int]:
        return list(self._rows.get(owner, ()))

    def table_row(self, owner: int, width: int) -> np.ndarray:
        """The owner's int32 block-table row, zero-padded (trash block) to
        `width` entries — the fixed-shape row a [B, max_blocks] device
        table carries per batch slot."""
        blocks = self._rows.get(owner, ())
        if len(blocks) > width:
            raise ValueError(f"owner {owner} holds {len(blocks)} blocks "
                             f"> table width {width}")
        row = np.zeros((width,), dtype=np.int32)
        row[:len(blocks)] = blocks
        return row

    # --------------------------------------------------------- accounting
    def occupancy(self, live_tokens: int) -> float:
        """TRUE-token occupancy: live (attended) KV rows over pooled
        capacity. This is the gauge that proves paging — padded-slot
        accounting can't go above the padding ratio."""
        return live_tokens / max(self.capacity_tokens, 1)

    def slots_occupancy(self) -> float:
        """Block-granular occupancy: allocated blocks over capacity (the
        continuity analog of the old padded-slot gauge — includes
        within-block padding and worst-case reservations)."""
        return self.used_blocks / max(self.capacity_blocks, 1)

    def reset(self):
        self._free = list(range(self.num_blocks - 1, 0, -1))
        self._rows.clear()
        self._refs.clear()
        self._notify()

    def _notify(self):
        cb = self.on_change
        if cb is not None:
            try:
                cb()
            except Exception:   # noqa: BLE001 — an observability observer
                pass            # must never take the allocator down

    # ------------------------------------------- spill payloads (ISSUE 14)
    def _spill_sig(self) -> tuple:
        from ..distributed import mesh as _mesh
        return ("spill_scatter", self.num_blocks, self.block_size,
                self.num_layers, self.block_shapes,
                str(self.dtype), self.cache_dtype,
                _mesh.mesh_axis_size("mp"))

    def read_block(self, pools, block: int) -> tuple:
        """ONE block's payload gathered to host — the spill tier's
        device→host serialization. Every layer's planes for `block` are
        stacked device-side into one array per storage dtype (f32 pools:
        one [2L, bs, H, D] stack; int8 pools: an int8 code stack plus an
        f32 scale stack) and fetched in a single `jax.device_get` call,
        so a spill costs one transfer per payload array, not one per
        layer. Returns the tuple of host ndarrays `write_block` takes
        back verbatim — the round trip is bit-identical by construction
        (same bytes, no recompute).

        SHARD CONSISTENCY (ISSUE 16): on head-sharded pools the
        `device_get` GATHERS across the mp shards, so the host payload
        is always the full-width ``[2L, bs, H, D]`` array regardless of
        shard count — a block spilled by an mp=4 engine rehydrates
        bit-identically into an mp=1 (or mp=2) pool and vice versa. The
        fleet spill tier's codec is therefore shard-count-independent by
        construction (gather-on-spill / reshard-on-rehydrate)."""
        import jax
        import jax.numpy as jnp
        if self.cache_dtype == "int8":
            codes = jnp.stack([layer[i][block] for layer in pools
                               for i in (0, 2)])
            scales = jnp.stack([layer[i][block] for layer in pools
                                for i in (1, 3)])
            return tuple(jax.device_get((codes, scales)))  # lint: allow(device-get)
        planes = jnp.stack([p[block] for layer in pools for p in layer])
        return (jax.device_get(planes),)  # lint: allow(device-get)

    def write_block(self, pools, block: int, payload: tuple):
        """Scatter one spilled payload back into pool position `block` —
        the REHYDRATE path: one host→device copy per payload array (the
        stacked planes ship as a single jit input), one donated in-place
        executable shared by every pool of this geometry. The block id
        is a data input, so rehydrating any block reuses the same
        compiled program. Returns the replaced pools (the old ones are
        donated/consumed).

        On head-sharded pools the full-width host payload enters as a
        replicated jit input and the scatter RE-SHARDS it: the updated
        pool keeps the operand's head-sharding (each shard writes only
        its own H-slice of the payload), so rehydration never moves pool
        bytes across shards. The executable cache key includes the mp
        axis size — engines at different shard counts never share a
        scatter program."""
        sig = self._spill_sig()
        fn = _SPILL_SCATTER_CACHE.get(sig)
        if fn is None:
            from ..jit.api import (SPILL_PROGRAM, _note_cache_miss,
                                   named_program)
            _note_cache_miss()     # a new serving executable, counted
            # exactly like the models' compiled-runner builds
            if self.cache_dtype == "int8":
                def run(pools, blk, codes, scales):
                    return [(kc.at[blk].set(codes[2 * i]),
                             ks.at[blk].set(scales[2 * i]),
                             vc.at[blk].set(codes[2 * i + 1]),
                             vs.at[blk].set(scales[2 * i + 1]))
                            for i, (kc, ks, vc, vs) in enumerate(pools)]
            else:
                n = len(self.block_shapes)

                def run(pools, blk, planes):
                    return [tuple(p.at[blk].set(planes[n * i + j])
                                  for j, p in enumerate(layer))
                            for i, layer in enumerate(pools)]
            fn = _SPILL_SCATTER_CACHE[sig] = named_program(
                run, SPILL_PROGRAM, donate_argnums=(0,))
        return fn(pools, np.int32(block), *payload)

    def __repr__(self):
        return (f"BlockPool(blocks={self.num_blocks}x{self.block_size}, "
                f"free={self.free_blocks}/{self.capacity_blocks}, "
                f"owners={len(self._rows)})")


# one scatter executable per pool geometry, shared across engines (all
# replicas of one model share shapes, so one compile serves the fleet);
# the state planes' row mover is kept here too
_SPILL_SCATTER_CACHE: Dict[tuple, object] = {}

STATE_ZERO, STATE_SAVE, STATE_LOAD = 0, 1, 2


class HostSpillTier:
    """Host-RAM budget + stats for spilled prefix blocks (ISSUE 14).

    The PrefixCache owns the trie-side mechanics (which node spills,
    where payloads live, LRU ordering); this class is the ACCOUNTING the
    capacity model and the metrics surface need: a byte budget charged
    at ``bytes_per_block`` per spilled block (the host copy carries the
    same payload bytes as the device block), occupancy, and the
    spill/rehydrate/drop/copy counters the smoke tests pin. Cached-
    prefix capacity becomes host-memory-sized instead of HBM-sized: an
    LRU-evicted full block serializes here instead of vanishing, and a
    later trie hit rehydrates it with one host→device copy — orders
    cheaper than recomputing its prefill."""

    def __init__(self, *, bytes_per_block: int, byte_budget: int):
        if byte_budget < bytes_per_block:
            raise ValueError(
                f"spill byte_budget {byte_budget} holds zero blocks "
                f"(one block = {bytes_per_block} bytes)")
        self.bytes_per_block = int(bytes_per_block)
        self.byte_budget = int(byte_budget)
        self.spilled_blocks = 0       # resident in the tier right now
        self.spilled_total = 0        # blocks ever serialized to host
        self.rehydrated_total = 0     # blocks copied back to device
        self.dropped_total = 0        # tier-LRU final deaths (payload
        #                               discarded for good)
        self.upgraded_total = 0       # spilled entries replaced in
        #                               place by a recomputed device
        #                               block (prefix survives — NOT a
        #                               drop)
        self.d2h_copies = 0           # host arrays fetched (spill side)
        self.h2d_copies = 0           # host arrays shipped (rehydrate)

    @property
    def capacity_blocks(self) -> int:
        return self.byte_budget // self.bytes_per_block

    @property
    def host_bytes(self) -> int:
        return self.spilled_blocks * self.bytes_per_block

    @property
    def over_budget_blocks(self) -> int:
        """Blocks the tier must drop to get back under budget."""
        return max(0, self.spilled_blocks - self.capacity_blocks)

    def stats(self) -> dict:
        return {"spilled_blocks": self.spilled_blocks,
                "host_bytes": self.host_bytes,
                "byte_budget": self.byte_budget,
                "spilled_total": self.spilled_total,
                "rehydrated_total": self.rehydrated_total,
                "dropped_total": self.dropped_total,
                "upgraded_total": self.upgraded_total,
                "d2h_copies": self.d2h_copies,
                "h2d_copies": self.h2d_copies}

    def metrics_text(self, prefix: str = "paddle_tpu_spill") -> str:
        """Prometheus exposition of the tier — registered beside the
        serving producers in `ServingEngine.metrics_registry()`."""
        from ..profiler._metrics import counter_lines, gauge_lines
        lines: List[str] = []
        for name, help_ in (
                ("spilled", "prefix blocks serialized to host RAM"),
                ("rehydrated", "spilled blocks copied back to device"),
                ("dropped", "spilled blocks evicted from the host tier "
                            "(payload lost for good)"),
                ("upgraded", "spilled entries replaced in place by a "
                             "recomputed device block"),
                ("d2h_copies", "device->host payload arrays (spill)"),
                ("h2d_copies", "host->device payload arrays (rehydrate)")):
            attr = name if name.endswith("copies") else f"{name}_total"
            lines.extend(counter_lines(prefix, f"{name}_total",
                                       getattr(self, attr), help_))
        lines.extend(gauge_lines(prefix, "host_blocks",
                                 self.spilled_blocks,
                                 "spilled blocks resident in host RAM"))
        lines.extend(gauge_lines(prefix, "host_bytes", self.host_bytes,
                                 "host RAM the spill tier pins"))
        return "\n".join(lines) + "\n"

    def __repr__(self):
        return (f"HostSpillTier(blocks={self.spilled_blocks}/"
                f"{self.capacity_blocks}, bytes={self.host_bytes}/"
                f"{self.byte_budget})")
