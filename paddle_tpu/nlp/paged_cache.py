"""Paged KV-cache pool manager (host-side block allocator).

The reference's blocked serving cache (paddle/incubate/nn/functional/
block_multihead_attention + PaddleNLP's BlockInferencePredictor —
unverified, SURVEY.md §0/§2.5) allocates fixed-size KV blocks from a
shared pool so HBM scales with LIVE tokens, not batch × max_seq_len.
The allocator is plain host Python (a free list); the device side is the
pool arrays + int32 block tables consumed by
``ops/pallas/paged_attention``.

CONTENT-ADDRESSED PREFIX CACHING (``prefix_cache=True``): full token
blocks are published into an index keyed by a rolling hash that CHAINS
over the prefix — a block's key folds its parent's key, so identical
block content at different prefix depths never collides — and every
bucket entry stores its (parent, token-tuple) key material, so even a
forced hash collision verifies before it aliases. A new sequence whose
prompt walks a cached chain ALIASES those physical blocks into its
table (``attach_prefix`` — the refcounted ``share()`` primitive per
block), paying neither prefill compute nor fresh residency for them;
the first token WRITTEN into a shared block triggers copy-on-write
(``make_writable``: allocate fresh, copy the pool rows, decref the
shared block). The index itself holds one refcount per published
block, so a cached block survives its sequences and is reclaimed —
LRU, leaf-first so chains stay walkable — only under allocation
pressure and only at refcount one (no live holder).
"""
from __future__ import annotations

import zlib

import numpy as np
import jax
import jax.numpy as jnp

__all__ = ["PagedKVCachePool", "prompt_prefix_key"]

_FNV_OFFSET = 0xCBF29CE484222325
_FNV_PRIME = 0x100000001B3
_MASK64 = (1 << 64) - 1


def _chain_hash(parent_hash, tokens):
    """Rolling FNV-1a over one block's token ids, seeded by the PARENT
    block's chain hash — depth is part of the key, so the same content
    at a different prefix depth hashes differently. Collisions are
    still verified against the stored key material before any alias
    (tests force this function to a constant to prove it)."""
    h = (int(parent_hash) ^ _FNV_OFFSET) & _MASK64
    for t in tokens:
        h ^= int(t) & 0xFFFFFFFF
        h = (h * _FNV_PRIME) & _MASK64
    return h


def prompt_prefix_key(tokens, block_size, max_blocks=None):
    """Public content-address of a prompt's leading FULL blocks — the
    exact chain key :class:`PagedKVCachePool`'s prefix index stores for
    the same tokens, so a router keyed on it never alias-routes to a
    replica whose cache would miss.

    Chains :func:`_chain_hash` from the root (parent hash 0) over each
    full ``block_size`` slice, identically to the pool's internal
    ``_match_entries`` walk.  The trailing partial block never enters
    the pool's index and never enters the key.  ``max_blocks`` caps the
    walk (routers hash only the leading blocks for speed); ``None``
    hashes every full block.

    Returns the final 64-bit chain hash, or ``None`` when the prompt
    has no full block (nothing cacheable to be affine to).
    """
    bs = int(block_size)
    if bs <= 0:
        raise ValueError(f"block_size must be positive, got {bs}")
    n = len(tokens) // bs
    if max_blocks is not None:
        n = min(n, int(max_blocks))
    if n <= 0:
        return None
    h = 0
    for i in range(n):
        h = _chain_hash(h, tuple(int(t) for t in tokens[i * bs:(i + 1) * bs]))
    return h


class _PrefixEntry:
    """One published full block: a node of the prefix-chain trie. The
    index holds ONE refcount on ``block`` for as long as the entry
    lives; ``parent`` identity + the token tuple are the verified key
    material behind the chain hash."""

    __slots__ = ("hash", "parent", "tokens", "block", "nchildren",
                 "tick")

    def __init__(self, hash_, parent, tokens, block, tick):
        self.hash = hash_
        self.parent = parent
        self.tokens = tokens
        self.block = block
        self.nchildren = 0
        self.tick = tick


class PagedKVCachePool:
    """A shared K/V block pool + per-sequence block tables.

    Args:
        num_blocks: pool capacity in blocks (shared by all sequences).
        block_size: tokens per block (lane-friendly: 16/32/64...).
        num_kv_heads, head_dim, num_layers: cache geometry.
        layout: ``"kv"`` (a K and a V array a layer) or ``"latent"``:
            ONE array a layer whose row is the token's compressed
            key/value (latent attention: ``num_kv_heads`` 1,
            ``head_dim`` the latent width plus the rotary key's),
            stored ``(num_blocks, block_size, head_dim)``: there is no
            head axis. (A TPU keeps such an array block-index-minor
            while ``head_dim`` is not whole 128-lane tiles, as 576 is
            not, and re-lays it out round a program that reads blocks:
            PERF.md section 6, PR 33.) The V
            side is then an EMPTY list exactly as the scale pools are
            empty on a float pool (zero avals), so ``adopt``,
            ``commit_like``, copy-on-write, the accounting and the
            donation audit hold for both. A model says which it caches
            (``model.paged_cache_layout()``).
        state: the SLOT side, for a model with layers whose cache does
            not grow with the context (a state-space layer's recurrence,
            a window-attention layer's ring of keys): ``{"slots": S,
            "layers": n, "arrays": [(shape, dtype), ...]}`` gives ``n``
            state layers, each a tuple of arrays ``(S, *shape)`` whose
            row ``s`` is what the request in slot ``s`` carries from
            token to token, whatever its context (``dtype`` None: the
            pool's ``dtype``). A None in a ``shape`` is a RING's length,
            ``"ring_tokens"`` of the same dict (what the engine works out
            from the model's window, its ``prefill_chunk`` and
            ``block_size``): that array holds a window layer's last
            positions, position ``p`` at row ``p mod ring_tokens``.
            ``num_layers`` then
            counts only the layers that hold block arrays. The side is
            EMPTY for a model without such layers (zero avals, as the V
            side of a latent pool), and is donated, adopted, committed
            and accounted with the blocks. Rows are never reset from the
            host: the program starts a row whose base length is 0 from
            zeros.
        dtype: cache dtype (bf16 for serving).
        kv_dtype: ``"int8"`` switches the block buffers to int8 and
            grows per-layer SCALE POOLS ``k_scales``/``v_scales`` of
            shape (num_blocks, block_size, num_kv_heads) float32 — one
            symmetric abs-max quant scale per written KV row, computed
            in-graph at every write site and consumed by the in-kernel
            dequant. Scale rows travel with their block: COW copies
            them, sharing aliases them, eviction reclaims them, and the
            mesh layout pins their kv-head axis exactly like the block
            buffers (``P(None, None, "mp")``). ``None`` keeps the
            float pool.
        mesh: optional ``jax.sharding.Mesh`` with an ``"mp"`` axis. The
            pool arrays are placed head-sharded across it
            (``P(None, None, "mp", None)`` — each chip holds every
            block for ITS KV heads), so block ids, tables, refcounts,
            prefix chains, and COW stay plain host bookkeeping: sharing
            splits WITHIN a block along the head dim, never across
            blocks, so one logical block id aliases the same rows on
            every chip. Falls back to replication when ``num_kv_heads``
            does not divide by the mesh's ``mp`` size.
    """

    def __init__(self, num_blocks, block_size, num_kv_heads, head_dim,
                 num_layers=1, dtype=jnp.bfloat16, prefix_cache=False,
                 mesh=None, kv_dtype=None, layout="kv", state=None):
        if layout not in ("kv", "latent"):
            raise ValueError(
                f"unsupported pool layout {layout!r} (kv or latent)")
        if layout == "latent" and kv_dtype is not None:
            raise NotImplementedError(
                "a latent pool with kv_dtype='int8' is not supported: "
                "the latent row's one scale would be shared by the "
                "compressed values and the rotary key")
        if layout == "latent" and mesh is not None:
            raise NotImplementedError(
                "a latent pool under a mesh is not supported: its one "
                "row a token is shared by every head, so there is no "
                "head axis to shard")
        if state and (kv_dtype is not None or mesh is not None
                      or prefix_cache):
            raise NotImplementedError(
                "a pool with slot state (a state-space layer's "
                "recurrence, a window layer's ring) does not compose with "
                "kv_dtype='int8', a mesh or the prefix cache: a cached "
                "block says nothing of the state or the ring its prefix "
                "left")
        self.layout = layout
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.num_kv_heads = int(num_kv_heads)
        self.head_dim = int(head_dim)
        self.num_layers = int(num_layers)
        if kv_dtype not in (None, "int8"):
            raise ValueError(
                f"unsupported kv_dtype {kv_dtype!r} (None or 'int8')")
        self.kv_dtype = kv_dtype
        shape = (self.num_blocks, self.block_size, self.num_kv_heads,
                 self.head_dim)
        if layout == "latent":
            if self.num_kv_heads != 1:
                raise ValueError(
                    "a latent pool holds one row a token: num_kv_heads "
                    f"must be 1, not {self.num_kv_heads}")
            shape = (self.num_blocks, self.block_size, self.head_dim)
        self.mesh = mesh
        self._pool_sharding = None
        self._scale_sharding = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            mp = int(mesh.shape.get("mp", 1))
            sharded = mp > 1 and self.num_kv_heads % mp == 0
            spec = (PartitionSpec(None, None, "mp", None)
                    if sharded else PartitionSpec())
            self._pool_sharding = NamedSharding(mesh, spec)
            sspec = (PartitionSpec(None, None, "mp")
                     if sharded else PartitionSpec())
            self._scale_sharding = NamedSharding(mesh, sspec)
        pool_dtype = jnp.int8 if self.quantized else dtype
        self.k_pools = [jnp.zeros(shape, pool_dtype)
                        for _ in range(num_layers)]
        self.v_pools = ([] if layout == "latent" else
                        [jnp.zeros(shape, pool_dtype)
                         for _ in range(num_layers)])
        if self._pool_sharding is not None:
            self.k_pools = [jax.device_put(p, self._pool_sharding)
                            for p in self.k_pools]
            self.v_pools = [jax.device_put(p, self._pool_sharding)
                            for p in self.v_pools]
        # per-row symmetric quant scales: one f32 per (block, position,
        # kv head), written in-graph alongside every int8 KV row and
        # consumed by the in-kernel dequant. Head axis pinned to the
        # same mesh split as the block buffers.
        if self.quantized:
            sshape = (self.num_blocks, self.block_size,
                      self.num_kv_heads)
            self.k_scales = [jnp.zeros(sshape, jnp.float32)
                             for _ in range(num_layers)]
            self.v_scales = [jnp.zeros(sshape, jnp.float32)
                             for _ in range(num_layers)]
            if self._scale_sharding is not None:
                self.k_scales = [jax.device_put(s, self._scale_sharding)
                                 for s in self.k_scales]
                self.v_scales = [jax.device_put(s, self._scale_sharding)
                                 for s in self.v_scales]
        else:
            self.k_scales = []
            self.v_scales = []
        # the slot side: per state layer a tuple of (slots, ...) arrays;
        # a None in a shape is the ring's length
        self.ring_tokens = int(state.get("ring_tokens", 0)) if state else 0
        self._ring_arrays = tuple(
            None in shape for shape, _ in state["arrays"]) if state else ()
        self.state = tuple(
            tuple(jnp.zeros((int(state["slots"]), *(
                self.ring_tokens if n is None else n for n in shape)),
                dt or dtype) for shape, dt in state["arrays"])
            for _ in range(int(state["layers"]))) if state else ()
        self._free = list(range(self.num_blocks - 1, -1, -1))
        self._tables: dict = {}   # seq_id -> list[int] block ids
        self._lens: dict = {}     # seq_id -> int tokens
        self._refcounts: dict = {}  # block id -> holders (>= 1 while out)
        self._peak_blocks = 0     # high-water mark of blocks_in_use
        self._freed_total = 0     # blocks returned over the pool's life
        # content-addressed prefix index (enable_prefix_cache)
        self._prefix_enabled = False
        self._prefix_buckets: dict = {}  # chain hash -> [_PrefixEntry]
        self._cached_blocks: dict = {}   # block id -> its entry
        self._prefix_tick = 0            # LRU clock for eviction
        self.prefix_hits = 0             # blocks served from the index
        self.prefix_misses = 0           # full blocks that had to be built
        self.cow_copies = 0              # copy-on-write block copies
        self.prefix_aliases = 0          # share() aliases the index created
        self.prefix_evictions = 0        # entries reclaimed under pressure
        # resilience tier (serving/faults.py + engine resilience=):
        # fault_hook fires inside _alloc_block (deterministic injected
        # allocation failures); kv_checksums arms the chain-hash
        # CONTENT verify — publish records a per-block checksum,
        # attach_prefix re-verifies before aliasing and QUARANTINES a
        # corrupted subtree; accounting_rebuilds counts degraded-mode
        # recoveries from refcount drift
        self.fault_hook = None
        self.kv_checksums = False
        self._block_crcs: dict = {}      # block id -> publish-time crc
        self.prefix_quarantines = 0      # entries dropped by verify
        self.accounting_rebuilds = 0
        if prefix_cache:
            self.enable_prefix_cache()

    @property
    def quantized(self):
        """True when the block buffers are int8 + per-row scale pools."""
        return self.kv_dtype == "int8"

    # -- allocator ---------------------------------------------------------
    def _alloc_block(self):
        """Pop one free block, reclaiming cached-only prefix blocks
        (LRU) when the free list runs dry — eviction under pressure
        respects refcounts: only an index-sole-holder block is taken.

        Blocks are born TRACKED: the refcount entry is written here,
        before the caller sees the id, so a stats snapshot taken
        mid-operation (e.g. during a COW device copy, which allocates
        and then copies layer by layer) can never observe an
        allocated-but-unaccounted block."""
        if self.fault_hook is not None:
            # deterministic fault injection: a raised hook fires BEFORE
            # any state changes, so the caller can simply retry
            self.fault_hook(self)
        if not self._free:
            self.evict_prefix(1)
        if not self._free:
            raise RuntimeError(
                f"KV pool exhausted ({self.num_blocks} blocks)")
        blk = self._free.pop()
        self._refcounts[blk] = 1
        return blk

    def ensure(self, seq_id, new_total_tokens):
        """Grow ``seq_id``'s block table to cover ``new_total_tokens``."""
        table = self._tables.setdefault(seq_id, [])
        need = -(-int(new_total_tokens) // self.block_size)
        while len(table) < need:
            table.append(self._alloc_block())
        self._lens[seq_id] = max(self._lens.get(seq_id, 0),
                                 int(new_total_tokens))
        self._peak_blocks = max(self._peak_blocks, self.blocks_in_use)
        return table

    def grow_decode_table(self, seq_id, need_tokens, written_tokens,
                          pad_to=None, cow=False):
        """Decode-dispatch pre-growth fused into ONE allocator call:
        grow ``seq_id``'s table to cover ``need_tokens`` (a K-quantum
        dispatch pre-grows K*T tokens ahead — admission already
        reserved the request's worst case, so K-wide growth can never
        oversubscribe the pool), copy-on-write the about-to-be-written
        range ``[written_tokens, need_tokens)`` when ``cow`` (prefix-
        cache engines must never write into a block another holder
        still maps), and return the padded host int32 table row the
        quantum dispatch feeds the device. The row is written on the
        host: a device array built and read back was one round trip a
        slot and quantum."""
        if need_tokens > self.seq_len(seq_id):
            self.ensure(seq_id, need_tokens)
        if cow:
            self.make_writable(seq_id, int(written_tokens),
                               int(need_tokens))
        table = self._tables.get(seq_id, [])
        row = np.zeros(max(len(table), pad_to or 1), np.int32)
        row[:len(table)] = table
        return row

    def share(self, src_seq_id, dst_seq_id):
        """Alias ``src``'s blocks into a new table for ``dst`` with the
        refcounts bumped — the content-reuse primitive (prefix cache /
        copy-on-write): each shared block only returns to the free list
        when its LAST holder releases it, so eviction of one holder can
        never free a block another sequence still maps."""
        if dst_seq_id in self._tables:
            raise ValueError(f"sequence {dst_seq_id!r} already exists")
        src = self._tables.get(src_seq_id)
        if src is None:
            raise KeyError(f"unknown sequence {src_seq_id!r}")
        for blk in src:
            self._refcounts[blk] += 1
        self._tables[dst_seq_id] = list(src)
        self._lens[dst_seq_id] = self._lens.get(src_seq_id, 0)
        return self._tables[dst_seq_id]

    # -- content-addressed prefix cache ------------------------------------
    def enable_prefix_cache(self):
        """Turn on the prefix index for this pool (off by default: the
        index, the attach/publish walk, and COW checks only run for
        pools that opted in, so an unshared pool's behavior — and its
        compiled consumers — are byte-identical)."""
        self._prefix_enabled = True

    @property
    def prefix_cache_enabled(self):
        return self._prefix_enabled

    @property
    def cached_blocks(self):
        """Blocks currently held by the prefix index (their content is
        addressable by chain hash; resident but reclaimable once no
        live sequence maps them)."""
        return len(self._cached_blocks)

    def _full_blocks(self, tokens):
        return len(tokens) // self.block_size

    def _block_tokens(self, tokens, i):
        bs = self.block_size
        return tuple(int(t) for t in tokens[i * bs:(i + 1) * bs])

    def _match_entries(self, tokens, max_blocks=None):
        """Walk ``tokens``' full blocks down the chain; return the
        longest VERIFIED entry chain (hash match alone never aliases —
        parent identity + token tuple must both compare equal)."""
        if not self._prefix_enabled:
            return []
        n = self._full_blocks(tokens)
        if max_blocks is not None:
            n = min(n, int(max_blocks))
        entries, parent, h = [], None, 0
        for i in range(n):
            blk_toks = self._block_tokens(tokens, i)
            h = _chain_hash(h, blk_toks)
            hit = None
            for e in self._prefix_buckets.get(h, ()):
                if e.parent is parent and e.tokens == blk_toks:
                    hit = e
                    break
            if hit is None:
                break
            entries.append(hit)
            parent = hit
        return entries

    def match_prefix(self, tokens):
        """Cached tokens a new sequence with this prompt could alias
        (a whole number of full blocks; 0 when the cache is off/cold)."""
        return len(self._match_entries(tokens)) * self.block_size

    def prefix_match_stats(self, tokens, max_blocks=None):
        """Admission-accounting view of a lookup: how many blocks would
        alias, and how many of those are currently EVICTABLE (index is
        the sole holder) — attaching pins them, so the scheduler's
        novel-demand check must move them out of the reclaimable set."""
        entries = self._match_entries(tokens, max_blocks=max_blocks)
        ev = sum(1 for e in entries if self._refcounts.get(e.block) == 1)
        return {"matched_blocks": len(entries),
                "matched_tokens": len(entries) * self.block_size,
                "evictable": ev}

    def attach_prefix(self, seq_id, tokens, max_blocks=None):
        """Alias the longest cached chain of ``tokens``' full blocks
        into a NEW table for ``seq_id`` (per-block ``share()``:
        refcounts bump, the sequence starts life ``matched_tokens``
        deep). Returns the aliased token count; also counts the lookup
        (hits = aliased blocks, misses = the prompt's other full
        blocks), so call it once per admission even on a cold cache."""
        if not self._prefix_enabled:
            return 0
        if seq_id in self._tables:
            raise ValueError(f"sequence {seq_id!r} already exists")
        entries = self._match_entries(tokens, max_blocks=max_blocks)
        if self.kv_checksums:
            entries = self._verify_entries(entries)
        self.prefix_hits += len(entries)
        self.prefix_misses += max(
            self._full_blocks(tokens) - len(entries), 0)
        if not entries:
            return 0
        self._prefix_tick += 1
        for e in entries:
            self._refcounts[e.block] += 1
            e.tick = self._prefix_tick
        self._tables[seq_id] = [e.block for e in entries]
        self._lens[seq_id] = len(entries) * self.block_size
        self.prefix_aliases += len(entries)
        return len(entries) * self.block_size

    def publish_prefix(self, seq_id, tokens):
        """Publish ``seq_id``'s now-written FULL blocks covering
        ``tokens`` into the index (called at prefill completion, when
        the host knows both the token ids and that their KV is in the
        pool). Each newly indexed block gains one refcount — the
        index's hold — so it outlives the sequence until evicted.
        Chain positions already indexed (by this sequence's own attach,
        or a racing twin) keep their existing entry. Returns the number
        of newly published blocks."""
        if not self._prefix_enabled:
            return 0
        table = self._tables.get(seq_id)
        if table is None:
            return 0
        n = min(self._full_blocks(tokens), len(table))
        self._prefix_tick += 1
        parent, h, published = None, 0, 0
        for i in range(n):
            blk_toks = self._block_tokens(tokens, i)
            h = _chain_hash(h, blk_toks)
            hit = None
            for e in self._prefix_buckets.get(h, ()):
                if e.parent is parent and e.tokens == blk_toks:
                    hit = e
                    break
            if hit is None:
                blk = table[i]
                if blk in self._cached_blocks:
                    # this physical block already backs another chain
                    # node — never double-index one block (the stats
                    # and eviction accounting assume block -> entry is
                    # one-to-one); stop publishing here
                    break
                hit = _PrefixEntry(h, parent, blk_toks, blk,
                                   self._prefix_tick)
                self._prefix_buckets.setdefault(h, []).append(hit)
                self._cached_blocks[blk] = hit
                self._refcounts[blk] += 1
                if parent is not None:
                    parent.nchildren += 1
                if self.kv_checksums:
                    self._block_crcs[blk] = self._block_crc(blk)
                published += 1
            else:
                hit.tick = self._prefix_tick
            parent = hit
        return published

    def make_writable(self, seq_id, start_token, end_token):
        """COPY-ON-WRITE: before a forward writes KV at positions
        ``[start_token, end_token)``, give ``seq_id`` exclusive
        ownership of every block in that range. A shared block
        (refcount > 1 — other sequences and/or the prefix index still
        map it) is replaced by a fresh block carrying a device-side
        copy of its pool rows, and the shared block is decref'd; the
        other holders never see the write. Returns the number of
        blocks copied (0 on exclusively-owned fast path)."""
        table = self._tables.get(seq_id)
        if not table or end_token <= start_token:
            return 0
        bs = self.block_size
        lo = max(int(start_token) // bs, 0)
        hi = min((int(end_token) - 1) // bs, len(table) - 1)
        copies = 0
        for j in range(lo, hi + 1):
            blk = table[j]
            if self._refcounts.get(blk, 1) <= 1:
                continue
            fresh = self._alloc_block()  # born refcounted
            for i in range(self.num_layers):
                self.k_pools[i] = self._pin(self.k_pools[i].at[fresh].set(
                    self.k_pools[i][blk]))
                if self.v_pools:
                    self.v_pools[i] = self._pin(
                        self.v_pools[i].at[fresh].set(self.v_pools[i][blk]))
                if self.quantized:
                    # the scale rows ARE the block's content on a
                    # quantized pool — a COW that left them behind
                    # would let the writer's new scales corrupt the
                    # sharer's dequantized values
                    self.k_scales[i] = self._pin_scale(
                        self.k_scales[i].at[fresh].set(
                            self.k_scales[i][blk]))
                    self.v_scales[i] = self._pin_scale(
                        self.v_scales[i].at[fresh].set(
                            self.v_scales[i][blk]))
            table[j] = fresh
            self._release([blk])
            copies += 1
            self.cow_copies += 1
        if copies:
            self._peak_blocks = max(self._peak_blocks,
                                    self.blocks_in_use)
        return copies

    def evictable_prefix_blocks(self):
        """Cached blocks reclaimable RIGHT NOW: the index is their sole
        holder (refcount == 1 — no live sequence maps them)."""
        return sum(1 for b in self._cached_blocks
                   if self._refcounts.get(b) == 1)

    def _drop_entry(self, e):
        bucket = self._prefix_buckets.get(e.hash, [])
        bucket.remove(e)
        if not bucket:
            self._prefix_buckets.pop(e.hash, None)
        if e.parent is not None:
            e.parent.nchildren -= 1
        del self._cached_blocks[e.block]
        self._block_crcs.pop(e.block, None)
        self._release([e.block])
        self.prefix_evictions += 1

    # -- resilience: content verify + degraded-mode recovery ---------------
    def _block_crc(self, blk):
        """Publish-time content checksum of one cached block: crc32
        over the layer-0 K rows (cheap; a cached block's pool content
        is immutable while cached — any write COWs first — so a
        mismatch at attach time means real corruption). On a quantized
        pool the scale rows are part of the content identity: the same
        int8 codes under different scales dequantize differently."""
        crc = zlib.crc32(np.asarray(self.k_pools[0][blk]).tobytes())
        if self.quantized:
            crc = zlib.crc32(
                np.asarray(self.k_scales[0][blk]).tobytes(), crc)
        return crc

    def _verify_entries(self, entries):
        """Chain-hash verify-mismatch ladder: re-checksum each matched
        cached block before aliasing it; the FIRST mismatch quarantines
        that entry's whole subtree (a corrupted parent poisons every
        descendant's content lineage) and truncates the match there —
        the sequence continues UNSHARED from that depth."""
        for i, e in enumerate(entries):
            want = self._block_crcs.get(e.block)
            if want is None or self._block_crc(e.block) == want:
                continue
            self.quarantine_prefix(e)
            return entries[:i]
        return entries

    def quarantine_prefix(self, entry):
        """Drop ``entry`` and every descendant from the prefix index
        (live sequences that already alias the blocks keep their
        refcounted holds — only the index's holds release). Returns
        the number of entries quarantined."""
        doomed = {id(entry): entry}
        changed = True
        while changed:
            changed = False
            for e in self._cached_blocks.values():
                if id(e) in doomed:
                    continue
                if e.parent is not None and id(e.parent) in doomed:
                    doomed[id(e)] = e
                    changed = True
        remaining = list(doomed.values())
        while remaining:
            leaves = [e for e in remaining if e.nchildren == 0]
            if not leaves:  # chains are trees; cannot happen
                raise RuntimeError("prefix subtree has no leaf")
            for e in leaves:
                self._drop_entry(e)
                remaining.remove(e)
        self.prefix_quarantines += len(doomed)
        return len(doomed)

    def rebuild_accounting(self):
        """Degraded-mode recovery from accounting drift: rebuild the
        refcount map and free list from the LIVE BLOCK TABLES — the
        only ownership structure tied to real sequence state — and
        conservatively drop the whole prefix index (cached subtrees
        cannot be trusted after drift; no ``_release`` walk, the index
        holds are simply forgotten). ``_check_accounting`` passes by
        construction afterwards. Returns a summary dict."""
        counts: dict = {}
        for blocks in self._tables.values():
            for b in blocks:
                counts[b] = counts.get(b, 0) + 1
        dropped_entries = len(self._cached_blocks)
        self._prefix_buckets = {}
        self._cached_blocks = {}
        self._block_crcs = {}
        self._refcounts = dict(counts)
        held = set(counts)
        self._free = [b for b in range(self.num_blocks - 1, -1, -1)
                      if b not in held]
        for s in list(self._lens):
            if s not in self._tables:
                del self._lens[s]
        self.accounting_rebuilds += 1
        return {"held_blocks": len(held),
                "free_blocks": len(self._free),
                "dropped_prefix_entries": dropped_entries}

    def evict_prefix(self, n):
        """Reclaim up to ``n`` cached blocks under allocation pressure:
        LRU over LEAF entries (no children — dropping a mid-chain node
        would orphan its descendants) whose block the index solely
        holds. A block a live sequence still maps is never touched
        (refcount > 1), so eviction can starve before ``n`` — the
        caller's exhaustion error stands. Returns blocks reclaimed."""
        freed = 0
        while freed < n:
            best = None
            for b, e in self._cached_blocks.items():
                if e.nchildren or self._refcounts.get(b) != 1:
                    continue
                if best is None or e.tick < best.tick:
                    best = e
            if best is None:
                break
            self._drop_entry(best)
            freed += 1
        return freed

    def clear_prefix_cache(self):
        """Release EVERY index hold (leaf-first so parents become
        droppable) — the leak-audit teardown: after the sequences are
        freed too, ``free_blocks`` must equal ``num_blocks`` and the
        refcount map must be empty."""
        dropped = 0
        while self._cached_blocks:
            leaves = [e for e in self._cached_blocks.values()
                      if e.nchildren == 0]
            if not leaves:  # cycle-proof: chains are trees, can't happen
                raise RuntimeError("prefix index has no leaf entries")
            for e in leaves:
                self._drop_entry(e)
                dropped += 1
        return dropped

    def _check_accounting(self):
        """Hard invariants tying the three ownership structures
        together (free list / refcount map / tables + prefix index):
        every non-free block is refcounted exactly once in the map, no
        block is simultaneously free and held, and every block a table
        or the index maps is tracked. Drift means a snapshot would
        double-count an in-flight block (the COW allocate-then-copy
        window) or hide a leak, so the stats methods raise instead of
        publishing numbers built on corrupt accounting."""
        held = set(self._refcounts)
        if len(held) != self.blocks_in_use:
            raise RuntimeError(
                f"pool accounting drift: {self.blocks_in_use} blocks "
                f"out of the free list but {len(held)} refcounted")
        stale = held & set(self._free)
        if stale:
            raise RuntimeError(
                f"blocks {sorted(stale)} are both free and refcounted")
        mapped = set(self._cached_blocks)
        for table in self._tables.values():
            mapped.update(table)
        untracked = mapped - held
        if untracked:
            raise RuntimeError(
                f"mapped blocks {sorted(untracked)} missing from the "
                f"refcount map")

    def prefix_cache_stats(self):
        """Monotonic counters + live index occupancy (the obs layer
        syncs the counters into the metrics registry at step
        boundaries)."""
        self._check_accounting()
        return {
            "hits": self.prefix_hits,
            "misses": self.prefix_misses,
            "cow_copies": self.cow_copies,
            "aliased_blocks": self.prefix_aliases,
            "evictions": self.prefix_evictions,
            "cached_blocks": self.cached_blocks,
            "evictable_blocks": self.evictable_prefix_blocks(),
        }

    def _release(self, blocks):
        """Refcount-safe return path shared by free/trim: decrement each
        block's holder count and only hand it back to the free list at
        zero. Double-release of a block this pool no longer tracks is a
        hard error (the eviction-leak class the serving tests pin)."""
        for blk in blocks:
            n = self._refcounts.get(blk)
            if n is None:
                raise RuntimeError(
                    f"block {blk} released but not held — double free")
            if n > 1:
                self._refcounts[blk] = n - 1
            else:
                del self._refcounts[blk]
                self._free.append(blk)
                self._freed_total += 1

    def free(self, seq_id):
        """Release a finished (or evicted) sequence's hold on its
        blocks; fully-released blocks return to the pool for immediate
        reuse (LIFO free list — straight to the next admission)."""
        blocks = self._tables.pop(seq_id, [])
        self._release(blocks)
        self._lens.pop(seq_id, None)

    def trim(self, seq_id, new_total_tokens):
        """Shrink (realloc) a live sequence to ``new_total_tokens``,
        releasing now-unused tail blocks — the speculative-decode
        rollback / prefix-truncation path. Growing is ``ensure``'s job;
        a trim above the current length is a no-op on the table."""
        table = self._tables.get(seq_id)
        if table is None:
            return []
        keep = -(-int(new_total_tokens) // self.block_size)
        released = table[keep:]
        del table[keep:]
        self._release(released)
        self._lens[seq_id] = min(self._lens.get(seq_id, 0),
                                 int(new_total_tokens))
        return released

    def blocks_needed(self, total_tokens):
        """Blocks a sequence of ``total_tokens`` occupies."""
        return -(-int(total_tokens) // self.block_size)

    def can_allocate(self, total_tokens):
        """Admission-control check: could a NEW sequence of
        ``total_tokens`` be allocated right now? Cached-only prefix
        blocks count as available — ``_alloc_block`` evicts them on
        demand when the free list runs dry."""
        return (self.blocks_needed(total_tokens)
                <= len(self._free) + self.evictable_prefix_blocks())

    def seq_len(self, seq_id):
        return self._lens.get(seq_id, 0)

    def held_blocks(self, seq_id):
        """Blocks ``seq_id``'s table currently maps (shared or
        exclusive) — the scheduler's novel-demand accounting subtracts
        this from a live request's worst-case demand."""
        return len(self._tables.get(seq_id, ()))

    @property
    def blocks_in_use(self):
        return self.num_blocks - len(self._free)

    @property
    def free_blocks(self):
        return len(self._free)

    def fragmentation_stats(self):
        """Allocator health counters for the serving scheduler: the only
        fragmentation a paged pool can have is INTERNAL (tail waste in
        each sequence's last block) — blocks are unit-sized so external
        fragmentation cannot occur. ``utilization`` is live tokens over
        allocated token capacity (1.0 when every allocated slot holds a
        live token).

        REFCOUNT-AWARE: a physical block shared by several sequences
        (prefix aliasing) is counted ONCE — its live coverage is the
        max any holder covers — and a cached-only block (held solely by
        the prefix index) counts as fully live; summing per-sequence
        lengths would claim utilization > 1 on a shared pool. For an
        unshared pool this reduces exactly to the old per-sequence
        sum."""
        self._check_accounting()
        bs = self.block_size
        coverage: dict = {}
        for s, table in self._tables.items():
            length = self._lens.get(s, 0)
            for j, blk in enumerate(table):
                c = min(bs, max(length - j * bs, 0))
                if c > coverage.get(blk, 0):
                    coverage[blk] = c
        for blk in self._cached_blocks:
            coverage[blk] = bs  # published blocks are full by contract
        live = sum(coverage.values())
        cap = self.blocks_in_use * self.block_size
        shared = sum(1 for n in self._refcounts.values() if n > 1)
        return {
            "num_blocks": self.num_blocks,
            "blocks_in_use": self.blocks_in_use,
            "free_blocks": len(self._free),
            "peak_blocks_in_use": self._peak_blocks,
            "blocks_freed_total": self._freed_total,
            "live_tokens": live,
            "tail_waste_tokens": cap - live,
            "utilization": (live / cap) if cap else 1.0,
            "shared_blocks": shared,
            "cached_blocks": len(self._cached_blocks),
            "kv_dtype": str(self.k_pools[0].dtype),
            "bytes_per_token": self.bytes_per_token(),
            "state_bytes_per_slot": self.state_bytes_per_slot(),
            "state_slots": self.state_slots,
            "window_bytes_per_slot": self.window_bytes_per_slot(),
            "window_ring_tokens": self.ring_tokens,
            "bytes_in_use": self.bytes_in_use(),
            "per_chip_bytes_in_use": self.per_chip_bytes_in_use(),
        }

    def _pin(self, arr):
        """Keep an eagerly-updated pool array on its mesh layout. The
        COW copy runs as eager ops whose output placement follows XLA's
        propagation; re-asserting the pool sharding here is a no-op
        when propagation already kept it and a reshard otherwise, so
        the donated quantum inputs never silently change layout."""
        if self._pool_sharding is None:
            return arr
        return jax.device_put(arr, self._pool_sharding)

    def _pin_scale(self, arr):
        """``_pin`` for the rank-3 scale pools (same head-axis split)."""
        if self._scale_sharding is None:
            return arr
        return jax.device_put(arr, self._scale_sharding)

    @property
    def tp_shards(self):
        """How many ways the KV-head dim is split across the mesh (1
        when unsharded/replicated)."""
        if self._pool_sharding is None or self.mesh is None:
            return 1
        if self._pool_sharding.spec == ():
            return 1
        return int(self.mesh.shape.get("mp", 1))

    def bytes_in_use(self):
        """Live cache bytes — the paged-cache memory claim: scales with
        allocated blocks, not batch × max_seq. Dtype-aware: computed
        from the ACTUAL buffer itemsize (int8 pools report half a
        bf16 pool's bytes) plus the scale-pool rows that travel with
        each quantized block."""
        return (self.bytes_per_token() * self.block_size
                * self.blocks_in_use
                + self.state_bytes_per_slot() * self._state_rows_in_use())

    @property
    def state_slots(self):
        """Rows of the slot side (0 without state layers)."""
        return self.state[0][0].shape[0] if self.state else 0

    def state_bytes_per_slot(self):
        """Bytes of slot state one request holds over all state layers,
        whatever its context (0 without state layers)."""
        return sum(a.dtype.itemsize * int(np.prod(a.shape[1:]))
                   for layer in self.state for a in layer)

    def window_bytes_per_slot(self):
        """The part of ``state_bytes_per_slot`` that is window layers'
        rings (0 without such layers)."""
        return sum(a.dtype.itemsize * int(np.prod(a.shape[1:]))
                   for layer in self.state
                   for a, ring in zip(layer, self._ring_arrays) if ring)

    def _state_rows_in_use(self):
        """Sequences that hold a slot's state: every one that holds
        blocks but the engine's own (ids that begin with ``__``, the
        scratch sequence)."""
        if not self.state:
            return 0
        return sum(1 for s in self._tables if not str(s).startswith("__"))

    @property
    def arrays_per_layer(self):
        """Block arrays a layer holds: K and V, or the one latent."""
        return 1 if self.layout == "latent" else 2

    def bytes_per_token(self):
        """Pool bytes one cached token takes over all layers (the scale
        rows of an int8 pool included): the pool's bytes over its token
        capacity."""
        per_row = (self.num_kv_heads * self.head_dim
                   * self.k_pools[0].dtype.itemsize)
        if self.quantized:
            per_row += self.num_kv_heads * self.k_scales[0].dtype.itemsize
        return self.arrays_per_layer * self.num_layers * per_row

    def per_chip_bytes_in_use(self):
        """Live cache bytes RESIDENT PER CHIP: under a head-sharded
        mesh layout each chip holds ``num_kv_heads / tp`` heads of
        every allocated block, so per-chip residency is the global
        claim divided by the shard count (exactly — the head dim must
        divide for the pool to shard at all)."""
        return self.bytes_in_use() // self.tp_shards

    def adopt(self, k_pools, v_pools, k_scales=(), v_scales=(), state=()):
        """Take a jitted step's donated-and-returned buffers as the
        pool's new truth (async handles: no sync). The scale pools of a
        float pool and the slot side of a pool without state layers are
        empty and stay so."""
        self.k_pools, self.v_pools = list(k_pools), list(v_pools)
        self.k_scales, self.v_scales = list(k_scales), list(v_scales)
        self.state = tuple(tuple(layer) for layer in state)

    def arrays(self):
        """The pool's sides as a jitted step takes (and donates) them:
        ``(k_pools, v_pools, k_scales, v_scales, state)``; a side the
        pool lacks is an empty pytree."""
        return (list(self.k_pools), list(self.v_pools),
                tuple(self.k_scales), tuple(self.v_scales), self.state)

    def commit_like(self, ref):
        """Give the buffers the commitment of ``ref``, a weight of the
        model that will write them. A jitted step's outputs are
        committed to a device when any of its inputs is, so beside
        committed weights a pool of fresh (uncommitted) zeros turns
        committed with its first dispatch, and every program that takes
        the pools would compile a second time the next time it runs.
        Under a mesh the buffers are committed to their layout from the
        start."""
        if self.mesh is None and ref.committed:
            self.adopt(*jax.tree_util.tree_map(
                lambda a: jax.device_put(a, ref.sharding), self.arrays()))

    # -- device views ------------------------------------------------------
    def block_table_array(self, seq_ids, pad_to=None):
        """(B, max_blocks) int32 table for the given sequences (dead
        entries = 0; they are predicated off by seq_lens)."""
        tables = [self._tables.get(s, []) for s in seq_ids]
        width = max([len(t) for t in tables] + [1])
        if pad_to:
            width = max(width, pad_to)
        out = np.zeros((len(seq_ids), width), np.int32)
        for i, t in enumerate(tables):
            out[i, : len(t)] = t
        return jnp.asarray(out)

    def seq_lens_array(self, seq_ids):
        return jnp.asarray([self._lens.get(s, 0) for s in seq_ids],
                           jnp.int32)
