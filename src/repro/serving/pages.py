"""Paged quantized KV cache with copy-on-write prefix sharing.

`SlotKVCache` (kvcache.py) gives every request a fixed `cache_len` row, so
HBM is reserved for each request's WORST-CASE context and two requests
sharing a system prompt store it twice.  This module replaces the row with
a PAGE TABLE over a global pool of fixed-size page blocks, which is the
refactor the paper's storage layout makes nearly free: the kv-quant block
machinery (PR 2, kernels/kv_dequant.py) packs codes + absmax scales along
the FEATURE dim only, never across tokens, so any page boundary on the
token axis yields self-contained packed pages — the layout is page-shaped
by construction, and a page can be shared, spilled, or restored as opaque
packed bytes.

Device layout — one `lm.init_caches(cfg, batch=n_pages, cache_len=ps,
per_slot=True)` tree, i.e. every leaf keeps the slot-pool shape with the
batch axis reinterpreted as PHYSICAL PAGES:

        k_packed  uint32 [n_p, n_pages, ps, n_words]
        k_scales  bf16   [n_p, n_pages, ps, n_blocks]   (+ v twin)
        pos       int32  [n_p, n_pages, ps]

    page_map   int32 [num_slots, P_max]   host-side, P_max = cache_len/ps

Sequence b's absolute position p lives in page ``page_map[b, p // ps]``
at offset ``p % ps`` — so gathering a sequence's pages in table order
(kernels/kv_dequant.gather_pages) reconstructs exactly the slot row, and
the decode read path is the UNCHANGED masked flash-decoding math on the
gathered view (models/attention.paged_decode_attention).  Token identity
with the unpaged path is therefore structural, not approximate.

Page 0 is the reserved TRASH page: never allocated, and every write that
must not land anywhere (idle decode rows, padded prefill positions,
masked COW pages) is redirected to it with pos = -1, mirroring the slot
pool's clamped idle writes.  The pool maintains the invariant that every
FREE page holds pos = -1 at all offsets (init_caches starts all -1; a
small jitted wipe re-establishes it when refcounts hit zero), so a page
popped from the free list is attention-invisible until real tokens are
scattered into it — no per-admission clearing pass.

Copy-on-write prefix sharing: after a prefill, every FULL prompt page is
``seal``ed under a key derived from the token prefix it holds (plus the
compile bucket — identical prefix bytes are only guaranteed within one
compiled prefill program).  A later admission whose prompt starts with
the same tokens ``fork``s from those sealed pages by refcount instead of
recomputing and re-storing them; its own writes (prompt tail, decode
positions) always target private pages, so fork-then-diverge never
aliases.  Preemption spills only the PRIVATE suffix (whole packed pages)
and retains the sealed prefix by refcount — restore is a full-page
scatter, bit-exact because pages move as stored.

Admission preallocates the whole worst case, ceil((L + max_new - 1)/ps)
pages (the final sampled token is returned, never written), so a running
request can never hit an out-of-pages wall mid-decode: admission control
is the ONLY place capacity is enforced, which is what "fragmentation-free
admission" means here.

``PageAllocator`` is the pure-host half (free list, refcounts, page
tables, the COW prefix index) with no jax dependency — the target of the
hypothesis property suite (tests/test_paged_pool.py): refcount
conservation, no leaks across retire/preempt/restore cycles, fork
isolation.  ``PagedKVPool`` wraps it around the device tree behind the
`SlotKVCache` interface so `Server` runs on either pool (docs/serving.md
#paged-kv-cache).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import attention as attn_mod
from repro.models import lm
from repro.serving.kvcache import SlotKVCache, _is_pos_leaf
from repro.serving.telemetry import NOOP


def prefix_page_keys(prompt, page_size: int, bucket: int) -> list:
    """COW keys for every FULL prompt page, in table order.

    Key i covers tokens [0, (i+1)*ps) — a page is only shareable together
    with everything before it, so keys embed the whole prefix, not just
    the page's own tokens.  The compile bucket is part of the key because
    bitwise-identical prefix K/V is only guaranteed between prefills of
    the SAME padded length (same compiled program; causal masking makes
    the prefix rows independent of the suffix *values*, but not of the
    program that computed them).  Exact tuples, not hashes: a hash
    collision would silently serve another request's context."""
    ps = page_size
    return [(bucket, tuple(prompt[: (i + 1) * ps]))
            for i in range(len(prompt) // ps)]


class PageAllocator:
    """Host-side page accounting: free list, refcounts, tables, COW index.

    Pure python over ints — no jax, no device state — so properties like
    refcount conservation and leak-freedom are checkable exhaustively by
    the hypothesis suite.  Page 0 (trash) is never handed out.

    An *owner* (request id) is in exactly one of two states here:
      - active: ``tables[owner]`` holds its full page table;
      - preempted: ``retained[owner]`` holds only the sealed shared
        prefix whose refcounts it keeps across the spill.
    """

    def __init__(self, n_pages: int, page_size: int):
        if n_pages < 2:
            raise ValueError("need >= 2 pages (page 0 is the trash page)")
        if page_size < 1:
            raise ValueError("page_size must be positive")
        self.n_pages = n_pages
        self.page_size = page_size
        # pop() -> lowest id first; page 0 excluded forever
        self.free: list[int] = list(range(n_pages - 1, 0, -1))
        self.ref: dict[int, int] = {}
        self.tables: dict[object, list[int]] = {}
        self.retained: dict[object, list[int]] = {}
        self.prefix_index: dict[object, int] = {}
        self.page_key: dict[int, object] = {}
        self.alloc_total = 0
        self.freed_total = 0
        self.cow_hits = 0

    # -- capacity ---------------------------------------------------------
    @property
    def n_free(self) -> int:
        return len(self.free)

    @property
    def n_usable(self) -> int:
        """Pool capacity excluding the trash page."""
        return self.n_pages - 1

    @property
    def n_shared(self) -> int:
        """Pages currently referenced by more than one sequence."""
        return sum(1 for c in self.ref.values() if c > 1)

    @property
    def n_resident(self) -> int:
        """Sequences holding pages (active + preempted retainers)."""
        return len(self.tables) + len(self.retained)

    def pages_needed(self, prompt_len: int, max_new: int) -> int:
        """Worst-case pages for a request: positions [0, L + max_new - 1)
        are written (prompt + all but the final sampled token)."""
        return -(-(prompt_len + max_new - 1) // self.page_size)

    def lookup(self, keys) -> list[int]:
        """Longest shareable prefix: sealed pages for a leading run of
        `keys`.  Stops at the first miss — page i is only usable together
        with pages 0..i-1."""
        out = []
        for k in keys:
            p = self.prefix_index.get(k)
            if p is None:
                break
            out.append(p)
        return out

    def can_admit(self, n_new: int) -> bool:
        return n_new <= self.n_free

    # -- lifecycle --------------------------------------------------------
    def admit(self, owner, keys, n_total: int):
        """Build `owner`'s table: fork the shareable prefix by refcount
        (COW), then pop fresh pages for the rest.  Returns
        (table, n_shared)."""
        assert owner not in self.tables and owner not in self.retained, \
            f"owner {owner!r} already holds pages"
        shared = self.lookup(keys)[:n_total]
        n_new = n_total - len(shared)
        if n_new > self.n_free:
            raise RuntimeError(
                f"out of pages: need {n_new} fresh, have {self.n_free} "
                f"(can_admit() is the admission gate)"
            )
        for p in shared:
            self.ref[p] += 1
        self.cow_hits += len(shared)
        fresh = [self.free.pop() for _ in range(n_new)]
        for p in fresh:
            self.ref[p] = 1
        self.alloc_total += n_new
        table = shared + fresh
        self.tables[owner] = table
        return table, len(shared)

    def seal(self, owner, keys) -> int:
        """Publish `owner`'s full prompt pages in the COW index (idempotent
        for pages another owner sealed first).  Returns pages newly
        sealed.  Must run before the owner can be preempted — a sealed
        prefix is what preemption retains."""
        table = self.tables[owner]
        sealed = 0
        for i, k in enumerate(keys):
            page = table[i]
            if k not in self.prefix_index:
                self.prefix_index[k] = page
                self.page_key[page] = k
                sealed += 1
        return sealed

    def private_suffix(self, owner) -> tuple[list[int], list[int]]:
        """(sealed prefix, private suffix) of an ACTIVE owner's table,
        read-only.  Sealed pages form a prefix of the table: admit()
        places shared pages first and seal() publishes table[0:n_keys]."""
        table = self.tables[owner]
        k = 0
        while k < len(table) and table[k] in self.page_key:
            k += 1
        return table[:k], table[k:]

    def detach_private(self, owner) -> list[int]:
        """Preempt: drop the private suffix (its contents are spilled by
        the caller FIRST), keep refcounts on the sealed prefix.  Returns
        the pages actually freed (refcount hit 0) for the device pos
        wipe."""
        prefix, private = self.private_suffix(owner)
        del self.tables[owner]
        self.retained[owner] = prefix
        return self._drop_all(private)

    def resume(self, owner, n_private: int) -> list[int]:
        """Un-preempt: re-allocate `n_private` fresh pages behind the
        retained prefix.  Returns the new full table."""
        prefix = self.retained.pop(owner)
        if n_private > self.n_free:
            self.retained[owner] = prefix
            raise RuntimeError(
                f"out of pages: resume needs {n_private}, have {self.n_free}"
            )
        fresh = [self.free.pop() for _ in range(n_private)]
        for p in fresh:
            self.ref[p] = 1
        self.alloc_total += n_private
        table = prefix + fresh
        self.tables[owner] = table
        return table

    def release(self, owner) -> list[int]:
        """Retire: drop every reference `owner` holds (active or
        preempted-retained).  Returns the pages freed for the device pos
        wipe.  Sealed pages leave the COW index the moment their last
        reference goes — sharing is between concurrently resident
        sequences only, so the index never pins HBM."""
        table = self.tables.pop(owner, None)
        if table is None:
            table = self.retained.pop(owner)
        return self._drop_all(table)

    def _drop_all(self, pages) -> list[int]:
        freed = []
        for p in pages:
            self.ref[p] -= 1
            if self.ref[p] == 0:
                del self.ref[p]
                key = self.page_key.pop(p, None)
                if key is not None:
                    del self.prefix_index[key]
                self.free.append(p)
                freed.append(p)
        self.freed_total += len(freed)
        return freed


def scatter_pages(pool, cc, pages, write_mask, length, page_size: int):
    """Scatter a batch-1 prefill cache `cc` (length Sb) into page-major
    pool leaves.  Pure/traceable — the server inlines it into its fused
    prefill-into-pages jit, the page twin of kvcache.scatter_row.

    ``pages`` [P_w] (P_w = Sb // ps) holds the physical page id for each
    logical prompt page; ``write_mask`` [P_w] is True exactly for the
    pages this request OWNS AND must fill — False entries (COW-shared
    prefix pages, which must never be rewritten, and bucket-padding pages
    past the prompt) are redirected to trash page 0 with stored pos -1.
    Position validity mirrors scatter_row: stored pos must satisfy
    0 <= p < length or the page row reads as empty."""
    pl_, treedef = jax.tree_util.tree_flatten_with_path(pool)
    cl, _ = jax.tree_util.tree_flatten_with_path(cc)
    target = jnp.where(write_mask, pages, 0)
    out = []
    for (path, pa), (_, ca) in zip(pl_, cl):
        if _is_pos_leaf(path):
            n_p, sb = ca.shape
            cw = ca.reshape(n_p, sb // page_size, page_size)
            valid = (cw >= 0) & (cw < length) & write_mask[None, :, None]
            out.append(pa.at[:, target].set(jnp.where(valid, cw, -1)))
        else:
            n_p, _, sb = ca.shape[:3]
            cw = ca[:, 0].reshape(
                (n_p, sb // page_size, page_size) + ca.shape[3:]
            )
            cw = jnp.where(
                write_mask.reshape((1, -1) + (1,) * (cw.ndim - 2)), cw, 0
            ).astype(cw.dtype)
            out.append(pa.at[:, target].set(cw))
    return jax.tree_util.tree_unflatten(treedef, out)


def paged_decode_attn_fn(page_map, page_size: int):
    """Build the ``decode_attn`` callback lm.decode_step threads to every
    attention layer, closing over a TRACED page_map [num_slots, P_max] —
    the server passes the current table snapshot as a jit argument each
    step, so table changes never recompile.  Write-then-read order and
    idle-row semantics match blocks.local_decode_attn exactly."""

    def decode_attn(q, k_new, v_new, cache, pos, *, cap=0.0, window=0,
                    kvq=None):
        assert window == 0, "paged serving requires full-cache attention"
        cache = attn_mod.write_cache_paged(
            cache, k_new, v_new, pos, page_map, page_size=page_size, kvq=kvq
        )
        o = attn_mod.paged_decode_attention(
            q, cache, pos, page_map, cap=cap, kvq=kvq
        )
        return o, cache

    return decode_attn


class PagedKVPool(SlotKVCache):
    """SlotKVCache interface over page-table storage (module docstring).

    ``num_slots`` keeps its meaning — the decode batch width, i.e. the
    max CONCURRENTLY DECODING sequences — but rows no longer cost
    cache_len of HBM each: KV bytes scale with ``n_pages`` alone, so a
    paged server can run 2-3x the rows in the slot pool's HBM budget
    (benchmarks/serve_bench.py --paged measures exactly this)."""

    def __init__(self, cfg, num_slots: int, cache_len: int,
                 dtype=jnp.bfloat16, *, page_size: int = 16,
                 n_pages: int | None = None, sharder=None, telemetry=NOOP):
        if page_size < 1 or (page_size & (page_size - 1)):
            raise ValueError(f"page_size must be a power of two, got {page_size}")
        if cache_len % page_size:
            raise ValueError(
                f"page_size {page_size} must divide cache_len {cache_len}"
            )
        if n_pages is None:
            # equal token capacity to the slot pool, plus the trash page
            n_pages = num_slots * (cache_len // page_size) + 1
        self.cfg = cfg
        self.num_slots = num_slots
        self.cache_len = cache_len
        self.page_size = page_size
        self.n_pages = n_pages
        self.pages_per_seq = cache_len // page_size  # P_max
        self.telemetry = telemetry
        # every pos leaf starts all -1: the free-page invariant holds at t0
        self.caches = lm.init_caches(cfg, n_pages, page_size, dtype,
                                     per_slot=True)
        if sharder is not None and sharder.mesh is not None \
                and not sharder.replicate:
            self.caches = jax.device_put(
                self.caches,
                sharder.cache_spec_tree(self.caches, n_pages, paged=True),
            )
        self._free = list(range(num_slots - 1, -1, -1))
        self._spill_fn = None
        self._restore_fn = None
        self._wipe_fn = None
        self.active = np.zeros(num_slots, dtype=bool)
        self.next_pos = np.full(num_slots, -1, dtype=np.int64)
        self.allocator = PageAllocator(n_pages, page_size)
        self.page_map = np.zeros((num_slots, self.pages_per_seq), np.int32)
        self._slot_meta: dict[int, dict] = {}
        if telemetry.enabled:
            self.record_footprint()

    def kv_read_counts(self, page_map) -> tuple[int, int]:
        """(kv_read, kv_live) of the decode step that gathers through
        `page_map`, the table passed to it: the read covers every page of
        every row (``paged_decode_attn_fn`` gathers the whole table,
        live or not), so its shape times the page size."""
        rows, pages = page_map.shape
        return rows * pages * self.page_size, self.live_positions()

    # -- admission planning (host) ---------------------------------------
    def pages_needed(self, prompt_len: int, max_new: int) -> int:
        return self.allocator.pages_needed(prompt_len, max_new)

    def can_admit_pages(self, prompt, max_new: int, bucket: int) -> bool:
        """Would a fresh admission of this request fit right now, given
        what its prefix can share?"""
        keys = prefix_page_keys(prompt, self.page_size, bucket)
        n_shared = len(self.allocator.lookup(keys))
        n_total = self.pages_needed(len(prompt), max_new)
        return self.allocator.can_admit(max(0, n_total - n_shared))

    def can_resume_pages(self, n_private: int) -> bool:
        return self.allocator.can_admit(n_private)

    def admit_pages(self, slot: int, owner, prompt, max_new: int,
                    bucket: int):
        """Allocate `slot`'s page table (COW fork + fresh pages) and
        return the prefill scatter operands: (n_shared, n_new,
        pages [P_w] int32, write_mask [P_w] bool) with P_w = bucket/ps."""
        assert self.active[slot], "admit_pages into a free slot"
        ps = self.page_size
        keys = prefix_page_keys(prompt, ps, bucket)
        n_total = self.pages_needed(len(prompt), max_new)
        table, n_shared = self.allocator.admit(owner, keys, n_total)
        n_prompt = -(-len(prompt) // ps)  # pages the prefill must cover
        self._slot_meta[slot] = {"owner": owner, "keys": keys}
        self.page_map[slot] = 0
        self.page_map[slot, : len(table)] = table
        p_w = bucket // ps
        pages = np.zeros(p_w, np.int32)
        pages[:n_prompt] = table[:n_prompt]
        write_mask = np.zeros(p_w, bool)
        write_mask[n_shared:n_prompt] = True
        self._page_gauges(alloc=n_total - n_shared, cow=n_shared)
        return n_shared, n_total - n_shared, pages, write_mask

    def seal_slot(self, slot: int) -> int:
        """Publish the slot's full prompt pages for COW (post-prefill)."""
        meta = self._slot_meta[slot]
        sealed = self.allocator.seal(meta["owner"], meta["keys"])
        self._page_gauges()
        return sealed

    # -- lifecycle overrides ----------------------------------------------
    def free(self, slot: int) -> int:
        """Release the occupant's pages (unless a preceding spill already
        detached them), wipe freed pages' pos rows, then free the row.
        Returns the number of pages freed (the page_release event)."""
        meta = self._slot_meta.pop(slot, None)
        n_freed = 0
        if meta is not None and meta["owner"] in self.allocator.tables:
            freed = self.allocator.release(meta["owner"])
            self._wipe_pages(freed)
            n_freed = len(freed)
        self.page_map[slot] = 0
        super().free(slot)
        self._page_gauges()
        return n_freed

    def room(self, slot: int) -> int:
        """Positions left inside the slot's ALLOCATED pages.  Full
        preallocation makes this > 0 for the whole sampled budget; the
        server still checks it as the clamped-write guard."""
        meta = self._slot_meta[slot]
        table = self.allocator.tables[meta["owner"]]
        return len(table) * self.page_size - int(self.next_pos[slot])

    def spill_slot(self, slot: int) -> dict:
        """Preempt: host-copy the PRIVATE page suffix (whole packed pages,
        never a dequantize) and drop those pages; the sealed shared
        prefix stays resident by refcount.  The spill record carries
        everything `restore_slot` needs to rebuild the table bit-exactly
        onto fresh pages."""
        from repro.core.packing import codes_per_word

        assert self.active[slot], "spill of a free slot"
        meta = self._slot_meta.pop(slot)
        owner = meta["owner"]
        prefix, private = self.allocator.private_suffix(owner)
        p_max = self.pages_per_seq
        pgs = np.zeros(p_max, np.int32)
        pgs[: len(private)] = private
        if self._spill_fn is None:
            self._spill_fn = jax.jit(lambda caches, pg: [
                leaf[:, pg] for leaf in jax.tree_util.tree_leaves(caches)])
        # one compiled gather + ONE host round trip; padding entries read
        # the trash page (pos -1 rows) and restore harmlessly to it
        rows = jax.device_get(self._spill_fn(self.caches, jnp.asarray(pgs)))
        freed = self.allocator.detach_private(owner)
        self._wipe_pages(freed)
        kv_keys = {"k", "v", "k_packed", "k_scales", "v_packed", "v_scales"}
        kv_bits = getattr(self.cfg, "kv_bits", 16) or 16
        frac = len(private) / max(p_max, 1)
        bytes_packed = 0
        bytes_logical = 0
        paths = jax.tree_util.tree_leaves_with_path(self.caches)
        for (path, _), row in zip(paths, rows):
            key = next((getattr(k, "key", None) for k in path
                        if getattr(k, "key", None) in kv_keys), None)
            if key is None:
                continue
            bytes_packed += int(row.nbytes * frac)
            if key in ("k", "v"):
                bytes_logical += int(row.size * frac) * 2
            elif key in ("k_packed", "v_packed"):
                bytes_logical += int(row.size * frac) * codes_per_word(kv_bits) * 2
        if self.telemetry.enabled:
            self.telemetry.inc("kv_spill_bytes_total", bytes_packed,
                               kind="packed")
            self.telemetry.inc("kv_spill_bytes_total", bytes_logical,
                               kind="logical")
        self._page_gauges()
        return {"rows": rows, "next_pos": int(self.next_pos[slot]),
                "owner": owner, "keys": meta["keys"],
                "n_private": len(private), "n_retained": len(prefix),
                "bytes_packed": bytes_packed, "bytes_logical": bytes_logical}

    def restore_slot(self, slot: int, spill: dict) -> None:
        """Resume: allocate fresh private pages, scatter the spilled page
        contents onto them (full-page writes cover pos, erasing whatever
        a previous tenant left), and rebuild the page table."""
        assert self.active[slot], "restore into a free slot — alloc first"
        owner = spill["owner"]
        table = self.allocator.resume(owner, spill["n_private"])
        fresh = table[spill["n_retained"]:]
        pgs = np.zeros(self.pages_per_seq, np.int32)
        pgs[: len(fresh)] = fresh
        if self._restore_fn is None:
            def _scatter(caches, rows, pg):
                leaves, treedef = jax.tree_util.tree_flatten(caches)
                new = [leaf.at[:, pg].set(row)
                       for leaf, row in zip(leaves, rows)]
                return jax.tree_util.tree_unflatten(treedef, new)
            self._restore_fn = jax.jit(_scatter, donate_argnums=0)
        self.caches = self._restore_fn(
            self.caches, list(spill["rows"]), jnp.asarray(pgs)
        )
        self._slot_meta[slot] = {"owner": owner, "keys": spill["keys"]}
        self.page_map[slot] = 0
        self.page_map[slot, : len(table)] = table
        self.next_pos[slot] = spill["next_pos"]
        self._page_gauges(alloc=spill["n_private"])

    # -- device pos wipe ---------------------------------------------------
    def _wipe_pages(self, pages) -> None:
        """Re-establish the free-page invariant (pos = -1 everywhere) on
        just-freed pages.  Padding the page vector with 0 keeps one
        compile; duplicate trash writes all store -1."""
        if not pages:
            return
        if self._wipe_fn is None:
            def _wipe(caches, pg):
                leaves, treedef = jax.tree_util.tree_flatten_with_path(caches)
                new = [pa.at[:, pg].set(-1) if _is_pos_leaf(path) else pa
                       for path, pa in leaves]
                return jax.tree_util.tree_unflatten(treedef, new)
            self._wipe_fn = jax.jit(_wipe, donate_argnums=0)
        p_max = self.pages_per_seq
        for i in range(0, len(pages), p_max):
            pgs = np.zeros(p_max, np.int32)
            chunk = pages[i: i + p_max]
            pgs[: len(chunk)] = chunk
            self.caches = self._wipe_fn(self.caches, jnp.asarray(pgs))

    # -- telemetry ---------------------------------------------------------
    def _page_gauges(self, alloc: int = 0, cow: int = 0) -> None:
        if not self.telemetry.enabled:
            return
        t = self.telemetry
        a = self.allocator
        t.set_gauge("kv_pages_total", a.n_usable)
        t.set_gauge("kv_pages_free", a.n_free)
        t.set_gauge("kv_pages_shared", a.n_shared)
        t.set_gauge("kv_pages_seqs_resident", a.n_resident)
        if alloc:
            t.inc("kv_pages_alloc_total", alloc)
        if cow:
            t.inc("kv_pages_cow_hits_total", cow)
        freed = a.freed_total - getattr(self, "_freed_seen", 0)
        if freed:
            t.inc("kv_pages_freed_total", freed)
        self._freed_seen = a.freed_total

    def record_footprint(self) -> None:
        super().record_footprint()
        self._page_gauges()
