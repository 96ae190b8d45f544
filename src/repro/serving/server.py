"""Continuous-batching server: slot pool + scheduler + jitted model steps.

Decode runs as ONE fixed-shape jitted step over the whole slot pool with
a per-row position vector: busy rows decode their own request at their
own position, idle rows are masked (pos=-1).  Between decode steps the
server admits queued requests into free slots by prefilling each new
prompt on its own (batch 1, padded to a compile-size bucket) and
scattering the resulting KV rows into the slot — requests join and leave
the decode batch mid-flight with no recompilation and no effect on the
other rows (docs/serving.md).

Admission order is SLA-aware (serving/scheduler.py): requests carry a
priority class (0 = most urgent), classes drain in per-class FIFO order
with optional anti-starvation aging, and — with ``max_preemptions > 0``
— an urgent arrival that finds the pool full can evict a lower-priority
victim by spilling its PACKED cache rows to host (codes + scales as
stored, no dequantize: ~kv_bits/16 of the bf16-equivalent bytes) and
restoring them bit-exactly later, so preempted token streams are
token-identical to an unpreempted run.  ``prefill_chunk=C`` splits long
prompts into fixed-size chunks interleaved with decode steps, bounding
how long one admission can stall the running batch; the committed rows
match a plain prefill bitwise (models/attention.prefill_chunk_attention)
so chunking never changes tokens.  All of this is host-side policy —
the jitted model steps are unchanged.

Restrictions: prompt-length bucketing (padding) is only enabled when
every mixer is full attention and the FFNs are dense — padded positions
are provably masked out of a causal full-attention cache, but would
corrupt SSM tail states and sliding-window ring buffers, and MoE
capacity dispatch is cross-token (junk tokens shift real tokens'
expert keep/drop), so those archs prefill at exact prompt length (one
compile per distinct length).

Passing ``sharder=`` serves the slot pool on a mesh: pool leaves are
placed sequence-sharded at construction (per-device KV bytes shrink by
the seq-shard degree — ``pool.kv_bytes()['per_device']``), the decode
step runs the sharder's shard_map flash-decoding with the PER-SLOT
position vector, and eligible quantized matmuls run column-parallel
inside ``sharder.tp_scope()``.  This composes with kv_bits: the packed
k-bit pool shards the same way (docs/serving.md#sharded-quantized-decode).

Works unchanged for quantized param trees: the decode/prefill fns are
the same lm.py entry points the static Engine uses, and quantization is
invisible above the in-layer dequant.

Passing ``telemetry=`` (serving/telemetry.py; defaults to the shared
no-op) turns the whole request lifecycle into spans and metrics:
submit -> queue-wait -> prefill -> per-step decode -> retire, with TTFT
and inter-token-latency histograms, queue/occupancy gauges, batch-fill
and padding-waste distributions, and quantization-health gauges.  All
instrumentation is host-side at the dispatch boundary (an explicit
``block_until_ready`` fence after the jitted call) — the compiled
programs are identical with telemetry on or off, so greedy outputs stay
token-identical (docs/observability.md, tests/test_telemetry.py).

The KV cache itself can be k-bit too (cfg.kv_bits in {4, 8}, e.g.
``cfg.with_kv_quant(4)``): pool leaves become packed codes + per-block
scales, each decode step append-quantizes the new token inside the same
jitted step, and the attention read path dequantizes (Pallas kernel on
TPU, jnp oracle on CPU) — kernels/kv_dequant.py, docs/serving.md.  The
pool pytree still never changes shape, so compile-once-per-bucket and
the scatter-based admission are untouched; ``pool.kv_bytes()`` shows
the ~16/k HBM saving that buys more slots or longer contexts.

``paged=True`` swaps the slot pool for a PAGE-TABLE pool
(serving/pages.py): KV storage becomes a global pool of fixed-size page
blocks with refcounted copy-on-write prefix sharing, so HBM is spent on
tokens actually stored — not per-slot worst cases — and requests sharing
a prompt prefix store it once.  The decode step gathers each row's pages
through its table (a traced argument — table churn never recompiles) and
runs the identical masked flash-decoding math on the gathered view, so
paged greedy outputs are token-identical to the slot pool at every
kv_bits.  Preemption spills only a request's PRIVATE page suffix and
retains the shared prefix by refcount.  Paged mode requires a
full-attention arch and is single-host; it composes with kv_bits because
quantized blocks run along the feature dim only, so packed pages are
self-contained (the paper's storage layout is page-shaped by
construction).
"""

from __future__ import annotations

import contextlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.kv_dequant import kv_spec
from repro.models import blocks, lm
from repro.models.sharding import check_decode_capability
from repro.serving.engine import sample_token
from repro.serving.kvcache import SlotKVCache, scatter_row, workspace_to_row
from repro.serving.pages import (
    PagedKVPool,
    paged_decode_attn_fn,
    scatter_pages,
)
from repro.serving.scheduler import Request, Scheduler
from repro.serving.telemetry import (
    NOOP,
    kv_roundtrip_error,
    record_quant_health,
    record_tree_bits,
)


def bucket_len(n: int, *, minimum: int = 8, cap: int | None = None) -> int:
    """Round up to a power of two so prefill compiles O(log max_len)
    times instead of once per distinct prompt length."""
    b = max(minimum, 1 << max(0, n - 1).bit_length())
    return min(b, cap) if cap is not None else b


#: flash_attention's KV-chunk size — chunked prefill is bitwise equal to
#: the plain prefill only while the whole bucketed prompt fits ONE KV
#: chunk of the flash scan (models/attention.prefill_chunk_attention);
#: longer buckets fall back to plain prefill per request.
_FLASH_KV_CHUNK = 1024


class _ChunkState:
    """Host-side progress of one chunked admission: the padded prompt,
    the per-chunk start offsets (the final start is shifted left so a
    fixed-size chunk never overruns the bucket — overlapped rows rewrite
    identical values), and the dense bf16 workspace the chunks write."""

    def __init__(self, *, req, slot, L, Sb, padded, starts, workspace, key,
                 t_start):
        self.req = req
        self.slot = slot
        self.L = L
        self.Sb = Sb
        self.padded = padded
        self.starts = starts
        self.workspace = workspace
        self.key = key
        self.t_start = t_start
        self.next = 0           # index of the next chunk to dispatch


def _bucketing_safe(cfg) -> bool:
    """Padded prefill is provably inert only when every mixer is causal
    full attention: SSM tail states and sliding-window ring buffers
    would absorb the padding.  MoE archs ARE bucketing-safe: the one
    cross-token padding hazard — junk tokens competing for expert
    capacity — is closed by the router pad mask the server threads into
    its prefill (models/moe.py pad_mask zeroes pads out of the dispatch
    count and uses the exact-length traced capacity), so real tokens
    keep/drop exactly as at exact length."""
    return all(
        m.startswith("attn") and blocks._mixer_window(m, cfg) == 0
        for m, _ in cfg.layer_schedule()
    )


class Server:
    """Continuous-batching front end: submit() requests, step() the
    engine (or run_until_drained()), receive per-request streamed tokens
    via callbacks."""

    def __init__(self, params, cfg, *, num_slots: int, max_seq_len: int,
                 eos_id: int | None = None, seed: int = 0,
                 dtype=jnp.bfloat16, plan=None,
                 matmul_mode: str | None = None, sharder=None,
                 telemetry=NOOP, prefill_chunk: int | None = None,
                 aging_steps: int | None = 64, max_preemptions: int = 0,
                 paged: bool = False, page_size: int = 16,
                 n_pages: int | None = None):
        if matmul_mode is not None:
            cfg = cfg.with_matmul_mode(matmul_mode)
        check_decode_capability(
            cfg, sharder,
            caller="the continuous-batching Server (serving/server.py)",
        )
        if paged:
            if not _bucketing_safe(cfg):
                raise ValueError(
                    "paged serving requires causal full attention in "
                    "every layer: SSM states and sliding-window ring "
                    "buffers do not decompose into position-indexed pages"
                )
            if prefill_chunk is not None:
                raise ValueError(
                    "prefill_chunk and paged are mutually exclusive (the "
                    "chunk workspace commits whole slot rows)"
                )
            if sharder is not None:
                raise ValueError(
                    "paged serving is single-host for now (the pool "
                    "itself places on a mesh via cache_spec_tree("
                    "paged=True); drop one of paged / sharder)"
                )
        elif n_pages is not None:
            raise ValueError("n_pages requires paged=True")
        if prefill_chunk is not None:
            if prefill_chunk < 1:
                raise ValueError("prefill_chunk must be >= 1")
            if not _bucketing_safe(cfg) or cfg.n_experts:
                # chunked prefill is stricter than bucketing: the dense
                # bf16 workspace runs each chunk through apply_layer_
                # prefill_chunk, which supports attn+MLP layers only —
                # MoE routing would mix chunk-local capacity decisions
                raise ValueError(
                    "prefill_chunk needs a bucketing-safe arch (causal "
                    "full attention, dense FFN): sliding windows and MoE "
                    "dispatch absorb chunk boundaries"
                )
            if sharder is not None:
                raise ValueError(
                    "prefill_chunk is single-device only (the chunk "
                    "workspace and commit path are unsharded); drop one "
                    "of prefill_chunk / sharder"
                )
        self.telemetry = telemetry
        if plan is not None:
            from repro.models.quantize import quantize_tree

            # load-time quantization health: per-matrix bits + blockwise
            # qerr, measured on the raw tree before it is consumed
            record_quant_health(telemetry, params, cfg, plan=plan)
            params = quantize_tree(params, cfg, plan=plan)
        else:
            record_tree_bits(telemetry, params)
        if sharder is not None:
            # extra decode room so full-attention cache lengths divide
            # the seq-shard grid (ring windows may still fall back)
            max_seq_len = sharder.pad_cache_len(max_seq_len)
        self.params = params
        self.cfg = cfg
        self.eos_id = eos_id
        self.sharder = sharder
        self.kvq = kv_spec(cfg)  # None = bf16 cache; else packed k-bit
        self._paged = paged
        self._page_size = page_size if paged else None
        if paged:
            self.pool = PagedKVPool(cfg, num_slots, max_seq_len, dtype,
                                    page_size=page_size, n_pages=n_pages,
                                    telemetry=telemetry)
        else:
            self.pool = SlotKVCache(cfg, num_slots, max_seq_len, dtype,
                                    sharder=sharder, telemetry=telemetry)
        self.scheduler = Scheduler(eos_id=eos_id, telemetry=telemetry,
                                   aging_steps=aging_steps,
                                   max_preemptions=max_preemptions)
        self._key = jax.random.PRNGKey(seed)
        self._bucketed = _bucketing_safe(cfg)
        self._prefill_chunk = prefill_chunk
        self._chunking: dict[int, _ChunkState] = {}   # slot -> progress
        self._spilled: dict[int, dict] = {}           # request id -> spill
        self._cur_tok = np.zeros(num_slots, dtype=np.int64)
        self._temps = np.zeros(num_slots, dtype=np.float32)
        self.steps = 0          # decode steps executed (virtual clock)
        self.tokens_out = 0     # total generated tokens
        constrain = sharder.constrain if sharder is not None else lm.NO_CONSTRAIN
        q_pad = sharder.head_pad() if sharder is not None else None
        tp_scope = sharder.tp_scope if sharder is not None \
            else contextlib.nullcontext
        # setup-time decode-attention decision: non-dividing cache lengths
        # warn ONCE here (SeqShardFallbackWarning), not inside the trace
        decode_attn = (sharder.decode_attn_fn(num_slots, max_seq_len)
                       if sharder is not None else blocks.local_decode_attn)

        # MoE archs bucket safely only with the router pad mask (junk
        # tokens would otherwise compete for expert capacity — moe.py);
        # exact-length prefills (unbucketed archs) keep pad_mask=None so
        # their grouped dispatch stays byte-identical to the Engine's
        use_pad_mask = bool(cfg.n_experts) and self._bucketed

        def prefill_into_slot(params, pool, prompt, length, slot, key,
                              temperature):
            """Fused admission: prefill [1, Sb], sample the first token
            at the TRUE last prompt position length-1 (padded tail
            positions are causally downstream and cannot affect it), and
            scatter the KV rows into `slot` — one dispatch per
            admission, no full-cache intermediate leaving the jit."""
            pm = ((jnp.arange(prompt.shape[1], dtype=jnp.int32)[None, :]
                   < length) if use_pad_mask else None)
            with tp_scope():
                h, caches, _ = lm.backbone_seq(
                    params, prompt, cfg, constrain=constrain, q_pad=q_pad,
                    write_cache=True, cache_len=max_seq_len, pad_mask=pm,
                )
                h_last = jax.lax.dynamic_index_in_dim(h, length - 1, 1,
                                                      keepdims=False)
                logits = lm.logits_from_hidden(params, h_last, cfg)
            tok = sample_token(logits, key, temperature)
            pool = scatter_row(pool, caches, slot, length)
            return tok, pool

        self._prefill = jax.jit(prefill_into_slot, donate_argnums=(1,))

        def step(params, tok, caches, pos, key, temps):
            with tp_scope():
                logits, caches = lm.decode_step(
                    params, tok, caches, pos, cfg,
                    constrain=constrain, decode_attn=decode_attn,
                )
            nxt = sample_token(logits, key, temps)
            return nxt, caches

        self._step = jax.jit(step, donate_argnums=(2,))

        if paged:
            def prefill_into_pages(params, pool, prompt, length, pages,
                                   write_mask, key, temperature):
                """Paged twin of prefill_into_slot: prefill [1, Sb] at its
                own length (the page scatter reshapes the Sb rows into
                Sb/ps pages), sample the first token at length-1, scatter
                the private prompt pages (write_mask True) and send the
                COW-shared prefix and bucket padding to trash page 0."""
                pm = ((jnp.arange(prompt.shape[1], dtype=jnp.int32)[None, :]
                       < length) if use_pad_mask else None)
                h, caches, _ = lm.backbone_seq(
                    params, prompt, cfg, write_cache=True, pad_mask=pm,
                )
                h_last = jax.lax.dynamic_index_in_dim(h, length - 1, 1,
                                                      keepdims=False)
                logits = lm.logits_from_hidden(params, h_last, cfg)
                tok = sample_token(logits, key, temperature)
                pool = scatter_pages(pool, caches, pages, write_mask,
                                     length, page_size)
                return tok, pool

            self._prefill_paged = jax.jit(prefill_into_pages,
                                          donate_argnums=(1,))

            def step_paged(params, tok, caches, pos, key, temps, page_map):
                """Decode step over page-major caches: the page table
                snapshot is a TRACED argument, so admissions/retires that
                rewrite it never recompile — the compiled program is the
                same masked flash-decoding math on the gathered view."""
                da = paged_decode_attn_fn(page_map, page_size)
                logits, caches = lm.decode_step(
                    params, tok, caches, pos, cfg, decode_attn=da,
                )
                nxt = sample_token(logits, key, temps)
                return nxt, caches

            self._step_paged = jax.jit(step_paged, donate_argnums=(2,))

        # optional roofline attribution (serving/profiler.py): a private
        # cost-cache session labelled with this server's quant config.
        # All host-side — the jitted programs above are byte-identical
        # with the profiler on.
        prof = getattr(telemetry, "profiler", None)
        self._prof = (prof.session(telemetry.registry,
                                   kv_bits=str(cfg.kv_bits),
                                   matmul_mode=cfg.matmul_mode)
                      if telemetry.enabled and prof is not None else None)

        if prefill_chunk is not None:
            # dense bf16 workspace config for the chunk K/V (the packed
            # encode happens ONCE at commit, exactly like plain prefill)
            self._cfg16 = cfg.with_kv_quant(16)

            def chunk_step(params, workspace, tokens, chunk_start):
                """One prefill chunk: C rows at traced chunk_start write
                their K/V into the workspace and attend over it.
                chunk_start is traced, so one compile covers every chunk
                of every prompt in the same bucket."""
                with tp_scope():
                    h, workspace = lm.backbone_chunk(
                        params, tokens, workspace, chunk_start, cfg,
                        constrain=constrain,
                    )
                return h, workspace

            self._chunk_step = jax.jit(chunk_step, donate_argnums=(1,))

            def chunk_commit(params, pool, workspace, h, last_rel, length,
                             slot, key, temperature):
                """Final-chunk epilogue: sample the first token at the
                true last prompt row and scatter the (re-encoded)
                workspace into `slot` — the committed row is bitwise the
                row a plain prefill admission would have written."""
                h_last = jax.lax.dynamic_index_in_dim(h, last_rel, 1,
                                                      keepdims=False)
                logits = lm.logits_from_hidden(params, h_last, cfg)
                tok = sample_token(logits, key, temperature)
                cc = workspace_to_row(workspace, max_seq_len, self.kvq)
                pool = scatter_row(pool, cc, slot, length)
                return tok, pool

            # donate the pool only: the outputs are (token, pool), so the
            # workspace has no same-shaped output to alias into — donating
            # it is an unfulfillable claim (analysis.audit rejects donated
            # leaves absent from input_output_alias); it dies by refcount
            # when the chunk state is dropped right after commit
            self._chunk_commit = jax.jit(chunk_commit, donate_argnums=(1,))

        # append-quantize health probe (telemetry.kv_probe_every > 0 and a
        # quantized cache): a SEPARATE bf16-cache prefill jit whose K/V
        # rows are round-tripped through the spec's encode/dequant on the
        # host — the serving jits above are untouched.
        self._probe = None
        self._n_admitted = 0
        if (telemetry.enabled and telemetry.kv_probe_every > 0
                and self.kvq is not None):
            cfg16 = cfg.with_kv_quant(16)

            def probe_caches(params, prompt):
                with tp_scope():
                    _, caches, _ = lm.backbone_seq(
                        params, prompt, cfg16, constrain=constrain,
                        q_pad=q_pad, write_cache=True, cache_len=max_seq_len,
                    )
                return caches

            self._probe = jax.jit(probe_caches)
            self._kv_err_sum = 0.0
            self._kv_err_n = 0

    def _probe_kv_error(self, padded, length: int) -> None:
        """Measure the append-quantize roundtrip error on this prompt's
        actual K/V rows (bf16 reference prefill -> encode_rows ->
        dequant) and fold it into the cumulative gauges."""
        caches = self._probe(self.params, padded)
        tel = self.telemetry
        for path, leaf in jax.tree_util.tree_leaves_with_path(caches):
            if not any(getattr(k, "key", None) in ("k", "v") for k in path):
                continue
            rows = leaf[:, 0, : min(length, leaf.shape[2])]
            feat = rows.shape[-2] * rows.shape[-1]
            rows = rows.reshape(-1, feat)
            err = kv_roundtrip_error(rows, self.kvq)
            self._kv_err_sum += err
            self._kv_err_n += 1
            tel.inc("kv_probe_rows_total", rows.shape[0])
            g = tel.registry.gauge("kv_append_qerr_max")
            if err > g.value:
                g.set(err)
        tel.set_gauge("kv_append_qerr_rms",
                      self._kv_err_sum / max(self._kv_err_n, 1))

    # -- API ---------------------------------------------------------------
    def submit(self, prompt, max_new: int, *, temperature: float = 0.0,
               arrival_time: float = 0.0, priority: int = 0,
               on_token=None) -> int:
        prompt = np.asarray(prompt, dtype=np.int64).reshape(-1)
        if len(prompt) == 0:
            raise ValueError("empty prompt")
        # positions [0, L + max_new - 1) are written: the prompt plus every
        # generated token EXCEPT the last, which is sampled and returned
        # but never fed back — so L + max_new - 1 == cache_len still fits
        # exactly (the old `L + max_new > cache_len` bound over-rejected
        # that boundary request by one position)
        if len(prompt) + max_new - 1 > self.pool.cache_len:
            raise ValueError(
                f"prompt {len(prompt)} + max_new {max_new} needs "
                f"{len(prompt) + max_new - 1} cache positions but the "
                f"budget is {self.pool.cache_len}"
            )
        if self._paged:
            need = self.pool.pages_needed(len(prompt), max_new)
            if need > self.pool.allocator.n_usable:
                raise ValueError(
                    f"request needs {need} pages worst-case but the pool "
                    f"holds {self.pool.allocator.n_usable} — it could "
                    f"never be admitted (raise n_pages or lower max_new)"
                )
        req = Request(prompt=prompt, max_new=max_new, temperature=temperature,
                      priority=priority, arrival_time=arrival_time,
                      on_token=on_token)
        # submit first: the scheduler assigns req.id (per-Scheduler
        # counter), which the trace event needs
        self.scheduler.submit(req)
        tel = self.telemetry
        if tel.enabled:
            req.t_submit = tel.now()
            tel.event("submit", req.t_submit, request_id=req.id,
                      step=self.steps, prompt_len=len(prompt),
                      max_new=max_new, arrival_time=arrival_time,
                      priority=priority)
        return req.id

    def step(self) -> int:
        """Admit arrived requests (preempting a lower-priority victim
        when the pool is full and preemption is enabled), advance one
        prefill chunk per chunking slot, then run one decode step over
        the non-chunking slots.  Returns the number of useful tokens
        produced (admission prefills included).

        Telemetry scopes cover the whole call: ``step`` around it,
        ``admit`` around admission, then ``decode_args`` / ``decode_step``
        / ``emit`` inside the decode (docs/observability.md)."""
        tel = self.telemetry
        with tel.scope("step", step=self.steps) as sc:
            with tel.scope("admit", step=self.steps):
                produced = self._admit()
            produced += self._advance_chunks()
            if len(self.scheduler.running) > len(self._chunking):
                produced += self._decode_once()
            if tel.enabled:
                sc.attrs["produced"] = produced
        self.steps += 1
        return produced

    def run_until_drained(self) -> dict:
        """Step until every submitted request has finished; the virtual
        clock jumps over idle gaps to the next arrival.  Returns
        {request_id: [tokens]}."""
        while not self.scheduler.drained:
            if not self.scheduler.running:
                nxt = self.scheduler.next_arrival()
                if nxt is not None and nxt > self.steps:
                    self.steps = int(np.ceil(nxt))
            self.step()
        return {r.id: list(r.tokens) for r in self.scheduler.finished}

    # -- internals ---------------------------------------------------------
    def _emit(self, req, tok: int) -> None:
        req.tokens.append(tok)
        self.tokens_out += 1
        tel = self.telemetry
        if tel.enabled:
            now = tel.now()
            tel.inc("serve_tokens_total")
            if req.t_first_token is None:
                req.t_first_token = now
                if req.t_submit is not None:
                    tel.observe("serve_ttft_seconds", now - req.t_submit)
                tel.event("token", now, request_id=req.id, step=self.steps,
                          first=True)
            elif req.t_last_token is not None:
                tel.observe("serve_itl_seconds", now - req.t_last_token)
            req.t_last_token = now
        if req.on_token is not None:
            req.on_token(req.id, tok)

    def _retire(self, req, slot: int, reason: str) -> None:
        self.scheduler.retire(slot, self.steps)
        n_freed = self.pool.free(slot)
        tel = self.telemetry
        if tel.enabled:
            now = tel.now()
            if self._paged:
                # before the retire event: the trace validator closes a
                # request's lifecycle at `retire` (trace.py)
                tel.event("page_release", now, request_id=req.id,
                          step=self.steps, n_pages=int(n_freed or 0),
                          reason=reason)
            tel.event("retire", now, request_id=req.id,
                      step=self.steps, n_tokens=len(req.tokens),
                      reason=reason)

    def _admit(self) -> int:
        produced = 0
        tel = self.telemetry
        while True:
            req = self.scheduler.next_admissible(self.steps)
            if req is None:
                break
            resume = req.id in self._spilled
            L = len(req.prompt)
            if self._paged:
                # the bucket floor is the page size so full prompt pages
                # tile the padded length (and join the COW key — pages.py)
                Sb = bucket_len(L, minimum=max(8, self._page_size),
                                cap=self.pool.cache_len)
            else:
                Sb = (bucket_len(L, cap=self.pool.cache_len)
                      if self._bucketed else L)

            def need_ok():
                """Row AND (paged) page availability for this admission."""
                if not self.pool.n_free:
                    return False
                if not self._paged:
                    return True
                if resume:
                    return self.pool.can_resume_pages(
                        self._spilled[req.id]["n_private"])
                return self.pool.can_admit_pages(req.prompt, req.max_new, Sb)

            blocked = False
            while not need_ok():
                # full pool (no row, or not enough pages): evict a strictly
                # lower-priority victim if preemption is on (mid-chunk slots
                # have no committed cache rows to spill and are never
                # victims); each eviction frees a row and its private
                # pages, so the loop terminates when victims run out
                vslot = self.scheduler.preemption_victim(
                    req, self.steps, exclude=self._chunking)
                if vslot is None:
                    blocked = True
                    break
                self._preempt(vslot, req)
            if blocked:
                break
            slot = self.pool.alloc()
            self.scheduler.bind(req, slot, self.steps)
            if resume:
                self._resume(req, slot)
                continue
            if (self._prefill_chunk is not None and L > self._prefill_chunk
                    and Sb <= _FLASH_KV_CHUNK):
                self._start_chunked(req, slot, L, Sb)
                continue
            padded = np.zeros((1, Sb), dtype=np.int64)
            padded[0, :L] = req.prompt
            self._key, sub = jax.random.split(self._key)
            if self._paged:
                n_shared, n_new, pgs, wmask = self.pool.admit_pages(
                    slot, req.id, req.prompt, req.max_new, Sb)
                if tel.enabled:
                    tel.event("page_alloc", tel.now(), request_id=req.id,
                              step=self.steps, slot=slot, n_pages=n_new,
                              n_shared=n_shared)
                pf_fn = self._prefill_paged
                pf_args = (self.params, self.pool.caches, jnp.asarray(padded),
                           jnp.int32(L), jnp.asarray(pgs), jnp.asarray(wmask),
                           sub, jnp.float32(req.temperature))
                pf_name = f"prefill_paged[{Sb}]"
            else:
                pf_fn = self._prefill
                pf_args = (self.params, self.pool.caches, jnp.asarray(padded),
                           jnp.int32(L), jnp.int32(slot), sub,
                           jnp.float32(req.temperature))
                pf_name = f"prefill[{Sb}]"
            if self._prof is not None:
                # AOT cost extraction happens BEFORE t0 so the one-time
                # compile never pollutes the timed window
                self._prof.ensure_costed(pf_name, pf_fn, pf_args)
            if tel.enabled and req.t_submit is not None:
                tel.span("queue_wait", req.t_submit, tel.now(),
                         request_id=req.id, step=self.steps,
                         steps=float(self.steps - req.arrival_time))
            with tel.scope("prefill", request_id=req.id, step=self.steps,
                           slot=slot, prompt_len=L, padded_len=Sb) as pf:
                tok, new_pool = pf_fn(*pf_args)
                self.pool.install_prefill(slot, new_pool, L)
                if self._paged:
                    # publish the full prompt pages for COW before anything
                    # can preempt this slot (spill retains sealed pages only)
                    self.pool.seal_slot(slot)
                if tel.enabled:
                    # fence at the dispatch boundary: host-side timing
                    # only, the compiled prefill is untouched
                    jax.block_until_ready(tok)
            if tel.enabled:
                dt = pf.t1 - pf.t0
                if self._prof is not None:
                    self._prof.observe(pf_name, dt)
                tel.observe("serve_prefill_seconds", dt)
                tel.observe("serve_prefill_pad_frac", (Sb - L) / Sb)
                tel.inc("serve_prefills_total")
                self._n_admitted += 1
                if (self._probe is not None
                        and (self._n_admitted - 1) % tel.kv_probe_every == 0):
                    self._probe_kv_error(jnp.asarray(padded), L)
            first = int(tok[0])
            self._emit(req, first)
            produced += 1
            if self.scheduler.should_retire(req):
                self._retire(req, slot,
                             "budget" if len(req.tokens) >= req.max_new
                             else "eos")
            elif self.pool.room(slot) <= 0:
                # a full row must never join the decode batch: its write
                # would clamp into the last stored position and corrupt it
                # (unreachable while submit enforces the budget bound, but
                # cheap to keep as the install/room/retire boundary guard)
                self._retire(req, slot, "cache_full")
            else:
                self._cur_tok[slot] = first
                self._temps[slot] = req.temperature
        return produced

    def _preempt(self, slot: int, by: Request) -> None:
        """Evict the request in `slot` for higher-priority request `by`:
        copy its packed cache rows to host AS STORED (no dequantize —
        spill bytes are ~kv_bits/16 of the bf16-equivalent), requeue it,
        free the slot.  Restore is bit-exact, so its eventual token
        stream is identical to an unpreempted run (greedy)."""
        victim = self.scheduler.running[slot]
        tel = self.telemetry
        with tel.scope("spill", request_id=victim.id, step=self.steps,
                       slot=slot) as sp:
            spill = self.pool.spill_slot(slot)
            spill["cur_tok"] = int(self._cur_tok[slot])
            self._spilled[victim.id] = spill
            self.scheduler.preempt(slot, self.steps)
            self.pool.free(slot)
            if tel.enabled:
                # inside the scope: the lifecycle wants page_release and
                # preempt ahead of the spill span (trace.py)
                if self._paged:
                    tel.event("page_release", sp.t0, request_id=victim.id,
                              step=self.steps, n_pages=spill["n_private"],
                              reason="preempt")
                tel.event("preempt", sp.t0, request_id=victim.id,
                          step=self.steps, slot=slot, by=by.id,
                          n_tokens=len(victim.tokens))
                sp.attrs.update(bytes_packed=spill["bytes_packed"],
                                bytes_logical=spill["bytes_logical"])

    def _resume(self, req: Request, slot: int) -> None:
        """Write a preempted request's spilled rows back into its new
        slot and rejoin the decode batch exactly where it left off."""
        spill = self._spilled.pop(req.id)
        tel = self.telemetry
        with tel.scope("restore", request_id=req.id, step=self.steps,
                       slot=slot, bytes_packed=spill["bytes_packed"]) as rs:
            self.pool.restore_slot(slot, spill)
            self._cur_tok[slot] = spill["cur_tok"]
            self._temps[slot] = req.temperature
            if tel.enabled:
                jax.block_until_ready(
                    jax.tree_util.tree_leaves(self.pool.caches)[0])
                if self._paged:
                    tel.event("page_alloc", rs.t0, request_id=req.id,
                              step=self.steps, slot=slot,
                              n_pages=spill["n_private"],
                              n_shared=spill["n_retained"])

    def _start_chunked(self, req: Request, slot: int, L: int, Sb: int) -> None:
        """Begin a chunked admission: allocate the dense bf16 workspace
        and schedule fixed-size chunks.  The final chunk's start is
        shifted left to end exactly at the bucket edge (min((n-1)C,
        Sb-C)) so the fixed chunk shape never overruns the workspace —
        overlapped rows recompute and rewrite identical values."""
        C = self._prefill_chunk
        tel = self.telemetry
        padded = np.zeros((1, Sb), dtype=np.int64)
        padded[0, :L] = req.prompt
        n_chunks = -(-L // C)
        starts = [i * C for i in range(n_chunks - 1)]
        starts.append(min((n_chunks - 1) * C, Sb - C))
        self._key, sub = jax.random.split(self._key)
        workspace = lm.init_caches(self._cfg16, 1, Sb)
        t0 = tel.now() if tel.enabled else 0.0
        if tel.enabled and req.t_submit is not None:
            tel.span("queue_wait", req.t_submit, t0, request_id=req.id,
                     step=self.steps,
                     steps=float(self.steps - req.arrival_time))
        self._chunking[slot] = _ChunkState(
            req=req, slot=slot, L=L, Sb=Sb, padded=padded, starts=starts,
            workspace=workspace, key=sub, t_start=t0,
        )
        # masked out of the decode batch until commit: next_pos stays -1
        # (idle row) and the fed token is zeroed
        self._cur_tok[slot] = 0
        self._temps[slot] = req.temperature

    def _advance_chunks(self) -> int:
        """Dispatch one prefill chunk per chunking slot; commit slots
        whose final chunk just ran (sample the first token, scatter the
        packed rows into the pool, join the decode batch)."""
        produced = 0
        tel = self.telemetry
        for slot in list(self._chunking):
            st = self._chunking[slot]
            C = self._prefill_chunk
            c0 = st.starts[st.next]
            tokens = jnp.asarray(st.padded[:, c0:c0 + C])
            ck_args = (self.params, st.workspace, tokens, jnp.int32(c0))
            ck_name = f"prefill_chunk[{st.Sb}]"
            if self._prof is not None:
                self._prof.ensure_costed(ck_name, self._chunk_step, ck_args)
            with tel.scope("prefill_chunk", request_id=st.req.id,
                           step=self.steps, slot=slot, chunk=st.next,
                           chunk_start=c0, chunk_len=C,
                           padded_len=st.Sb) as ck:
                h, st.workspace = self._chunk_step(*ck_args)
                if tel.enabled:
                    jax.block_until_ready(h)
            if tel.enabled:
                if self._prof is not None:
                    self._prof.observe(ck_name, ck.t1 - ck.t0)
                tel.observe("serve_prefill_chunk_seconds", ck.t1 - ck.t0)
                tel.inc("serve_prefill_chunks_total")
            st.next += 1
            if st.next == len(st.starts):
                produced += self._commit_chunked(slot, st, h)
        return produced

    def _commit_chunked(self, slot: int, st: _ChunkState, h) -> int:
        req = st.req
        tel = self.telemetry
        del self._chunking[slot]
        cm_args = (self.params, self.pool.caches, st.workspace, h,
                   jnp.int32(st.L - 1 - st.starts[-1]), jnp.int32(st.L),
                   jnp.int32(slot), st.key, jnp.float32(req.temperature))
        cm_name = f"chunk_commit[{st.Sb}]"
        if self._prof is not None:
            self._prof.ensure_costed(cm_name, self._chunk_commit, cm_args)
        t0c = tel.now() if tel.enabled else 0.0
        tok, new_pool = self._chunk_commit(*cm_args)
        self.pool.install_prefill(slot, new_pool, st.L)
        if tel.enabled:
            jax.block_until_ready(tok)
            t1 = tel.now()
            if self._prof is not None:
                self._prof.observe(cm_name, t1 - t0c)
            # the lifecycle-required prefill span covers the whole
            # chunked admission (its prefill_chunk spans nest inside)
            tel.observe("serve_prefill_seconds", t1 - st.t_start)
            tel.observe("serve_prefill_pad_frac", (st.Sb - st.L) / st.Sb)
            tel.inc("serve_prefills_total")
            tel.span("prefill", st.t_start, t1, request_id=req.id,
                     step=self.steps, slot=slot, prompt_len=st.L,
                     padded_len=st.Sb, chunks=len(st.starts))
            self._n_admitted += 1
            if (self._probe is not None
                    and (self._n_admitted - 1) % tel.kv_probe_every == 0):
                self._probe_kv_error(jnp.asarray(st.padded), st.L)
        first = int(tok[0])
        self._emit(req, first)
        if self.scheduler.should_retire(req):
            self._retire(req, slot,
                         "budget" if len(req.tokens) >= req.max_new
                         else "eos")
        elif self.pool.room(slot) <= 0:
            # same install/room/retire boundary guard as plain admission
            self._retire(req, slot, "cache_full")
        else:
            self._cur_tok[slot] = first
            self._temps[slot] = req.temperature
        return 1

    def _decode_call(self):
        """(jitted decode step, its arguments) for the pool as it stands."""
        tok = jnp.asarray(np.where(self.pool.active, self._cur_tok, 0),
                          jnp.int32)
        pos = self.pool.pos_vector()
        temps = jnp.asarray(np.where(self.pool.active, self._temps, 0.0),
                            jnp.float32)
        self._key, sub = jax.random.split(self._key)
        ds_args = (self.params, tok, self.pool.caches, pos, sub, temps)
        if self._paged:
            # the table snapshot rides along as a traced argument — the
            # compiled step is table-agnostic, so admissions never recompile
            return self._step_paged, ds_args + (
                jnp.asarray(self.pool.page_map),)
        return self._step, ds_args

    def lower_decode(self):
        """The decode step lowered for the current pool (``.as_text()``
        shows which kernels the served program calls)."""
        step_fn, ds_args = self._decode_call()
        return step_fn.lower(*ds_args)

    def _decode_once(self) -> int:
        tel = self.telemetry
        attrs = {}
        with tel.scope("decode_args", step=self.steps):
            step_fn, ds_args = self._decode_call()
            if tel.enabled:
                # what the KV read covers, from the table actually passed
                kv_read, kv_live = (
                    self.pool.kv_read_counts(ds_args[-1]) if self._paged
                    else self.pool.kv_read_counts())
                n_active = self.pool.n_active
                attrs = dict(n_active=n_active,
                             batch_fill=n_active / self.pool.num_slots,
                             kv_read=kv_read, kv_live=kv_live)
        if self._prof is not None:
            self._prof.ensure_costed("decode_step", step_fn, ds_args)
        with tel.scope("decode_step", step=self.steps, **attrs) as ds:
            nxt, self.pool.caches = step_fn(*ds_args)
            if tel.enabled:
                # fence at the dispatch boundary (the np.asarray below
                # would sync anyway; the explicit fence makes the timed
                # quantity "dispatch to completion", never a lazy transfer)
                jax.block_until_ready(nxt)
        if tel.enabled:
            dt = ds.t1 - ds.t0
            if self._prof is not None:
                self._prof.observe("decode_step", dt)
            tel.observe("serve_decode_step_seconds", dt)
            tel.observe("serve_batch_fill", attrs["batch_fill"])
            tel.inc("serve_decode_steps_total")
        nxt = np.asarray(nxt)
        produced = 0
        with tel.scope("emit", step=self.steps):
            for slot, req in list(self.scheduler.running.items()):
                if slot in self._chunking:
                    continue    # mid-chunk: masked idle row, no token yet
                t = int(nxt[slot])
                self._emit(req, t)
                produced += 1
                self.pool.advance(slot)
                if self.scheduler.should_retire(req):
                    self._retire(req, slot,
                                 "budget" if len(req.tokens) >= req.max_new
                                 else "eos")
                elif self.pool.room(slot) <= 0:
                    self._retire(req, slot, "cache_full")
                else:
                    self._cur_tok[slot] = t
        return produced
