"""Slot-based KV-cache pool for continuous batching.

The pool owns ONE decode-batch worth of per-slot caches (lm.init_caches
with per_slot=True): every batch row is an independent *slot* holding one
in-flight request at its own absolute position.  The host side tracks
which slots are free, which request occupies each busy slot, and the
next decode position per slot; the device side is a single cache pytree
whose leaves never change shape — so the decode step compiles exactly
once regardless of arrival pattern (docs/serving.md).

Lifecycle of a slot:

    alloc() -> slot            O(1) host pop from the free list
    install_prefill(slot, ...) adopt the pool tree produced by the
                               server's fused prefill+scatter_row jit
    (decode steps write in place via per-row vector positions)
    free(slot)                 O(1) host push; no device work — the stale
                               row is masked by pos=-1 until re-prefilled

`scatter_row` also INVALIDATES cache entries the prefill did not
actually produce: prompts may be right-padded up to a compile bucket, and
padded positions >= prompt_len must read as empty (-1) or the slot would
attend to junk.  The validity test is on the *stored position values*
(0 <= p < prompt_len), which is correct for both full caches and
sliding-window ring caches.

k-bit caches (cfg.kv_bits in {4, 8}) change only the LEAVES: the pool
tree holds packed codes + per-block scales instead of dense k/v
(kernels/kv_dequant.py), every leaf still shaped [n_p, B, S_c, ...].
The generic row write in `scatter_row` moves packed leaves untouched,
and the pos-based invalidation covers them for free — a padded tail's
stale code words are unreachable behind pos=-1.  `kv_bytes()` reports
the resident HBM cost, the number the kv_bits knob exists to shrink
(docs/serving.md).

A ``sharder`` places the pool onto its mesh at construction: KV leaves
sequence-sharded (slots over the data axes when the pool divides them,
cache positions over "model" + the rest), so each device holds only
cache_len/seq_shards positions per slot — ``kv_bytes()['per_device']``
measures it, and kv_bits multiplies with it (4-bit cache on an 8-way
mesh = 1/(4×8) of the bf16 single-device resident bytes per device).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import lm
from repro.serving.telemetry import NOOP


def _is_pos_leaf(path) -> bool:
    return any(getattr(k, "key", None) == "pos" for k in path)


def scatter_row(pool, cc, slot, length):
    """Write a batch-1 prefill cache `cc` into row `slot` of the pool.
    Pure/traceable — the server inlines it into its fused
    prefill-into-slot jit.

    Leaves line up because both trees were built with the same cache_len:
    pool k/v [n_p, B, S_c, ...] vs cc k/v [n_p, 1, S_c, ...]; pool pos
    [n_p, B, S_c] vs cc pos [n_p, S_c] (shared layout from prefill).
    SSM state/conv leaves have no position axis and copy through the same
    generic row write.
    """
    pl, treedef = jax.tree_util.tree_flatten_with_path(pool)
    cl, _ = jax.tree_util.tree_flatten_with_path(cc)
    out = []
    for (path, pa), (_, ca) in zip(pl, cl):
        if _is_pos_leaf(path):
            valid = (ca >= 0) & (ca < length)
            out.append(pa.at[:, slot].set(jnp.where(valid, ca, -1)))
        else:
            out.append(pa.at[:, slot].set(ca[:, 0]))
    return jax.tree_util.tree_unflatten(treedef, out)


def workspace_to_row(workspace, cache_len: int, kvq):
    """Convert a dense bf16 chunked-prefill workspace (lm.init_caches of
    the kv16 twin config, batch 1, bucket length Sb) into the batch-1
    cache tree `scatter_row` expects from a plain prefill: leaves in the
    POOL's layout (length cache_len; packed codes + scales when `kvq` is
    a quantized spec).  Pure/traceable — the server inlines it into its
    chunk-commit jit.

    Bit-exactness contract: encode_rows here sees exactly the K/V rows
    write_cache_prefill would have encoded (same projections, blockwise
    over the feature dim only), so the committed packed row is identical
    to the plain path's.  Workspace `pos` is arange over the written
    prefix and -1 beyond; positions >= prompt_len are invalidated by
    scatter_row's validity mask exactly as plain padding is."""
    from repro.kernels.kv_dequant import encode_rows

    def place(x):
        full = jnp.zeros(x.shape[:2] + (cache_len,) + x.shape[3:], x.dtype)
        return jax.lax.dynamic_update_slice_in_dim(full, x, 0, axis=2)

    cc = []
    for layer in workspace:
        k, v, pos = layer["k"], layer["v"], layer["pos"]
        n_p, b1, sb = k.shape[:3]
        pos_full = jnp.full((n_p, cache_len), -1, jnp.int32)
        pos_full = jax.lax.dynamic_update_slice(pos_full, pos, (0, 0))
        if kvq is not None:
            feat = k.shape[-2] * k.shape[-1]
            kp, ks = encode_rows(k.reshape(n_p, b1, sb, feat), kvq)
            vp, vs = encode_rows(v.reshape(n_p, b1, sb, feat), kvq)
            cc.append({"k_packed": place(kp), "k_scales": place(ks),
                       "v_packed": place(vp), "v_scales": place(vs),
                       "pos": pos_full})
        else:
            cc.append({"k": place(k), "v": place(v), "pos": pos_full})
    return tuple(cc)


class SlotKVCache:
    """Fixed pool of `num_slots` decode slots over per-slot caches."""

    def __init__(self, cfg, num_slots: int, cache_len: int, dtype=jnp.bfloat16,
                 *, sharder=None, telemetry=NOOP):
        self.cfg = cfg
        self.num_slots = num_slots
        self.cache_len = cache_len
        self.telemetry = telemetry
        self.caches = lm.init_caches(cfg, num_slots, cache_len, dtype,
                                     per_slot=True)
        if sharder is not None and sharder.mesh is not None \
                and not sharder.replicate:
            self.caches = jax.device_put(
                self.caches, sharder.cache_spec_tree(self.caches, num_slots)
            )
        self._free = list(range(num_slots - 1, -1, -1))  # pop() -> lowest id
        self._spill_fn = None    # jitted row gather/scatter, compiled on
        self._restore_fn = None  # first preemption (slot is a traced arg)
        self.active = np.zeros(num_slots, dtype=bool)
        # absolute position of the NEXT token fed to each slot (-1 = idle)
        self.next_pos = np.full(num_slots, -1, dtype=np.int64)
        if telemetry.enabled:
            self.record_footprint()

    # -- host-side bookkeeping -------------------------------------------
    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_active(self) -> int:
        return int(self.active.sum())

    def alloc(self) -> int:
        if not self._free:
            raise RuntimeError("no free slots")
        slot = self._free.pop()
        assert not self.active[slot], f"slot {slot} double-alloc"
        self.active[slot] = True
        if self.telemetry.enabled:
            self.telemetry.set_gauge("serve_slots_active", self.n_active)
        return slot

    def free(self, slot: int) -> None:
        assert self.active[slot], f"slot {slot} double-free"
        self.active[slot] = False
        self.next_pos[slot] = -1
        self._free.append(slot)
        if self.telemetry.enabled:
            self.telemetry.set_gauge("serve_slots_active", self.n_active)

    # -- device-side cache ops -------------------------------------------
    def install_prefill(self, slot: int, new_caches, prompt_len: int) -> None:
        """Adopt a pool tree that already had `slot` scattered (the
        server's fused prefill-into-slot jit calls scatter_row inline,
        saving a dispatch and a full-cache intermediate per admission)."""
        assert self.active[slot], "install_prefill into a free slot"
        self.caches = new_caches
        self.next_pos[slot] = prompt_len

    def advance(self, slot: int) -> None:
        self.next_pos[slot] += 1

    def pos_vector(self) -> jnp.ndarray:
        """[num_slots] int32 decode positions; -1 marks idle rows (their
        cache writes land clamped with pos=-1 and their attention output
        is a masked zero — see models/attention.py)."""
        return jnp.asarray(np.where(self.active, self.next_pos, -1), jnp.int32)

    def live_positions(self) -> int:
        """Positions holding tokens that a decode step attends over: the
        sum of pos + 1 across the decoding rows (idle and mid-chunk rows
        sit at -1 and hold none)."""
        pos = self.next_pos[self.active]
        return int(np.sum(pos[pos >= 0] + 1))

    def kv_read_counts(self) -> tuple[int, int]:
        """(kv_read, kv_live) of the decode step built from this pool:
        the positions its KV read covers, every row's whole cache_len
        (``pos_vector`` masks, it does not narrow), and the live ones."""
        return self.num_slots * self.cache_len, self.live_positions()

    def room(self, slot: int) -> int:
        """Decode positions left before this slot hits the cache budget."""
        return self.cache_len - int(self.next_pos[slot])

    def spill_slot(self, slot: int) -> dict:
        """Copy row `slot` of every cache leaf to host, AS STORED — packed
        code words and absmax scales for quantized caches, never a
        dequantize — so a later `restore_slot` is bit-exact by
        construction and a kv4 spill moves ~4/16 of the bf16-equivalent
        bytes (the preemption economics the paper's storage argument
        implies).  Returns the spill record the server parks on the
        preempted request: leaf rows in tree_flatten order, the slot's
        next_pos, and packed/logical byte counts of the KV payload
        (pos + SSM leaves ride along for restore but are precision-
        invariant, so they count toward neither)."""
        from repro.core.packing import codes_per_word

        assert self.active[slot], "spill of a free slot"
        kv_keys = {"k", "v", "k_packed", "k_scales", "v_packed", "v_scales"}
        kv_bits = getattr(self.cfg, "kv_bits", 16) or 16
        if self._spill_fn is None:
            self._spill_fn = jax.jit(lambda caches, s: [
                leaf[:, s] for leaf in jax.tree_util.tree_leaves(caches)])
        # one compiled gather + ONE host round trip for the whole record
        # (a per-leaf device_get would pay a blocking sync per leaf)
        rows = jax.device_get(self._spill_fn(self.caches, slot))
        bytes_packed = 0
        bytes_logical = 0
        paths = jax.tree_util.tree_leaves_with_path(self.caches)
        for (path, _), row in zip(paths, rows):
            key = next((getattr(k, "key", None) for k in path
                        if getattr(k, "key", None) in kv_keys), None)
            if key is None:
                continue
            bytes_packed += row.nbytes
            if key in ("k", "v"):
                bytes_logical += row.size * 2
            elif key in ("k_packed", "v_packed"):
                bytes_logical += row.size * codes_per_word(kv_bits) * 2
        if self.telemetry.enabled:
            self.telemetry.inc("kv_spill_bytes_total", bytes_packed,
                               kind="packed")
            self.telemetry.inc("kv_spill_bytes_total", bytes_logical,
                               kind="logical")
        return {"rows": rows, "next_pos": int(self.next_pos[slot]),
                "bytes_packed": bytes_packed, "bytes_logical": bytes_logical}

    def restore_slot(self, slot: int, spill: dict) -> None:
        """Write a spill record back into (re-alloc'd) row `slot`.  Every
        stored position of the row is overwritten, so whatever a later
        occupant — or the idle-row decode write, which parks pos=-1 at a
        clamped index — left behind is erased; restore then resume is
        token-identical to never having been preempted (pinned by
        tests/test_serving.py)."""
        assert self.active[slot], "restore into a free slot — alloc first"
        n_leaves = len(jax.tree_util.tree_leaves(self.caches))
        assert n_leaves == len(spill["rows"]), "spill/pool layout mismatch"
        if self._restore_fn is None:
            def _scatter(caches, rows, s):
                leaves, treedef = jax.tree_util.tree_flatten(caches)
                new = [leaf.at[:, s].set(row)
                       for leaf, row in zip(leaves, rows)]
                return jax.tree_util.tree_unflatten(treedef, new)
            # donate the pool: one compiled program of in-place row
            # writes (unjitted .at[].set would copy every full leaf)
            self._restore_fn = jax.jit(_scatter, donate_argnums=0)
        self.caches = self._restore_fn(self.caches, list(spill["rows"]), slot)
        self.next_pos[slot] = spill["next_pos"]

    def kv_bytes(self) -> dict:
        """Resident HBM bytes of the pool's attention KV leaves (packed
        codes + scales for quantized caches, dense k/v otherwise; pos and
        SSM state excluded — they are identical across kv_bits).

        ``per_device`` sums each leaf's addressable-shard bytes: equal to
        ``total`` single-device, ``total / (batch×seq shards)`` on a mesh
        — the number that decides how many slots / how much context one
        chip's HBM actually holds.

        ``logical`` is the PRE-QUANTIZATION bf16-equivalent bytes of the
        same cached values (2 bytes per logical K/V element; packed code
        words expand by codes-per-word, scales contribute nothing), and
        ``compression`` = logical/total — the one place the compression
        ratio is computed (serve_bench, the kv_pool_* gauges, and the
        docs tables all read it from here)."""
        from repro.core.packing import codes_per_word

        kv_keys = {"k", "v", "k_packed", "k_scales", "v_packed", "v_scales"}
        kv_bits = getattr(self.cfg, "kv_bits", 16) or 16
        total = 0
        per_device = 0
        logical = 0
        for path, leaf in jax.tree_util.tree_leaves_with_path(self.caches):
            key = next((getattr(k, "key", None) for k in path
                        if getattr(k, "key", None) in kv_keys), None)
            if key is None:
                continue
            total += leaf.size * leaf.dtype.itemsize
            if key in ("k", "v"):
                logical += leaf.size * 2
            elif key in ("k_packed", "v_packed"):
                logical += leaf.size * codes_per_word(kv_bits) * 2
            sharding = getattr(leaf, "sharding", None)
            if sharding is not None:
                per_device += (
                    math.prod(sharding.shard_shape(leaf.shape))
                    * leaf.dtype.itemsize
                )
            else:
                per_device += leaf.size * leaf.dtype.itemsize
        return {
            "total": total,
            "per_device": per_device,
            "logical": logical,
            "compression": logical / max(total, 1),
            "per_slot": total / max(self.num_slots, 1),
            "per_token": total / max(self.num_slots * self.cache_len, 1),
        }

    def record_footprint(self) -> None:
        """Export kv_bytes() + slot occupancy as gauges (bytes are
        kind-labelled) — called at construction and re-callable after
        re-placement or a registry reset (serve_bench's warm pass)."""
        kvb = self.kv_bytes()
        t = self.telemetry
        t.set_gauge("kv_pool_bytes", kvb["total"], kind="packed")
        t.set_gauge("kv_pool_bytes", kvb["logical"], kind="logical")
        t.set_gauge("kv_pool_bytes", kvb["per_device"], kind="per_device")
        t.set_gauge("kv_pool_compression_x", kvb["compression"])
        t.set_gauge("serve_slots_total", self.num_slots)
        t.set_gauge("serve_slots_active", self.n_active)
