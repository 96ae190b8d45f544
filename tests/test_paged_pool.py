"""Property + parity suite for the paged KV cache (serving/pages.py).

Two layers, mirroring test_scheduler_properties.py:

* A virtual harness (`drive`) pushes the pure-host ``PageAllocator``
  through random interleavings of admit / seal / preempt / resume /
  release and checks the page-table invariants after EVERY operation:

  - refcount conservation: each page's refcount equals the number of
    page tables (active + preempted-retained) that contain it;
  - partition: free pages and referenced pages partition the usable
    pool (no page leaked, none handed out twice, trash page 0 never
    allocated);
  - COW index sanity: every sealed key points at a live referenced page
    and the reverse map agrees;
  - fork isolation: pages popped fresh at admit carry refcount 1, so a
    forked request's WRITE set can never alias another table (shared
    prefix pages are only ever in the read-only sealed region);
  - drain leak-freedom: once every owner is released the free list is
    whole again and the COW index is empty.

* Device-level parity: the paged Server's greedy streams are
  TOKEN-IDENTICAL to the slot-pool Server at kv16/8/4 — including
  across preemption (spill only the private page suffix, restore onto
  fresh pages) — and shared-prefix admissions hold more concurrent
  residents than the same HBM budget of slot rows (the capacity win
  serve_bench --paged measures).

Hypothesis runs derandomized with bounded examples so CI is
deterministic; without hypothesis only the property tests skip.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # property tests skip; parametrized cases still run
    HAVE_HYPOTHESIS = False

from repro.configs.registry import get_arch
from repro.kernels.kv_dequant import gather_pages
from repro.models import lm
from repro.analysis.audit import compile_count
from repro.serving import (
    NOOP,
    PageAllocator,
    PagedKVPool,
    Server,
    Telemetry,
    validate_events,
)
from repro.serving.pages import prefix_page_keys

CFG = get_arch("tiny-160k")


# -------------------------------------------------------------------------
# allocator invariants (checked after every operation)
# -------------------------------------------------------------------------

def check_allocator(a: PageAllocator) -> None:
    counts: dict[int, int] = {}
    for t in list(a.tables.values()) + list(a.retained.values()):
        for p in t:
            counts[p] = counts.get(p, 0) + 1
    assert counts == a.ref, "refcount conservation violated"
    assert 0 not in a.ref and 0 not in a.free, "trash page handed out"
    held = set(a.ref)
    assert held.isdisjoint(a.free), "page simultaneously free and referenced"
    assert len(a.free) + len(held) == a.n_usable, \
        "pages leaked or duplicated (free + held != usable)"
    assert a.alloc_total - a.freed_total == len(held)
    for k, p in a.prefix_index.items():
        assert a.page_key.get(p) == k, "COW index and reverse map disagree"
        assert p in a.ref, "sealed page with no live reference"


def drive(specs, seed, page_size, extra_pages, max_ops=300):
    """Random interleaving harness.  ``specs`` = [(prompt tuple,
    max_new)]; the pool is sized so the largest single request always
    fits an empty pool (admission control, not capacity, is under
    test)."""
    need = [PageAllocator(2, page_size).pages_needed(len(p), m)
            for p, m in specs]
    a = PageAllocator(max(need) + extra_pages + 1, page_size)
    rng = np.random.default_rng(seed)
    pending = list(range(len(specs)))
    active: dict[int, int] = {}      # owner -> spec index
    preempted: dict[int, int] = {}   # owner -> n_private at detach
    for _ in range(max_ops):
        if not (pending or active or preempted):
            break
        choices = (["admit"] if pending else []) \
            + (["preempt", "release"] if active else []) \
            + (["resume"] if preempted else [])
        op = choices[int(rng.integers(len(choices)))]
        if op == "admit":
            i = pending[0]
            prompt, mx = specs[i]
            keys = prefix_page_keys(prompt, page_size, bucket=64)
            n_total = a.pages_needed(len(prompt), mx)
            n_new = n_total - len(a.lookup(keys)[:n_total])
            if not a.can_admit(n_new):
                # full: evict or retire someone, like the server would
                owner = (int(rng.choice(list(active))) if active
                         else int(rng.choice(list(preempted))))
                a.release(owner)
                active.pop(owner, None)
                preempted.pop(owner, None)
                check_allocator(a)
                continue
            pending.pop(0)
            table, n_shared = a.admit(i, keys, n_total)
            assert len(table) == n_total
            for p in table[n_shared:]:
                # fork isolation: fresh pages are exclusively ours, so
                # our write set cannot alias any other owner's table
                assert a.ref[p] == 1 and p not in a.page_key
            a.seal(i, keys)
            active[i] = i
        elif op == "preempt":
            owner = int(rng.choice(list(active)))
            prefix, private = a.private_suffix(owner)
            freed = a.detach_private(owner)
            assert set(freed) <= set(private), \
                "preempt freed a sealed prefix page"
            del active[owner]
            preempted[owner] = len(private)
        elif op == "resume":
            owner = int(rng.choice(list(preempted)))
            n_private = preempted[owner]
            if a.can_admit(n_private):
                table = a.resume(owner, n_private)
                for p in table[len(table) - n_private:]:
                    assert a.ref[p] == 1
                del preempted[owner]
                active[owner] = owner
            else:
                a.release(owner)
                del preempted[owner]
        else:  # release
            owner = int(rng.choice(list(active)))
            a.release(owner)
            del active[owner]
        check_allocator(a)
    for owner in list(active):
        a.release(owner)
        check_allocator(a)
    for owner in list(preempted):
        a.release(owner)
        check_allocator(a)
    assert not a.ref and not a.prefix_index and not a.page_key
    assert a.n_free == a.n_usable, "drained pool must be whole again"
    return a


# -------------------------------------------------------------------------
# hypothesis: random traffic upholds every page-table invariant
# -------------------------------------------------------------------------

if HAVE_HYPOTHESIS:
    # tiny token alphabet so random prompts actually share prefixes
    prompt = st.lists(st.integers(0, 2), min_size=1, max_size=24)
    spec = st.tuples(prompt.map(tuple), st.integers(1, 6))

    @settings(max_examples=300, deadline=None, derandomize=True)
    @given(specs=st.lists(spec, min_size=1, max_size=12),
           seed=st.integers(0, 2**31 - 1),
           page_size=st.sampled_from([2, 4, 8]),
           extra_pages=st.integers(0, 10))
    def test_random_traffic_upholds_page_invariants(specs, seed, page_size,
                                                    extra_pages):
        drive(specs, seed, page_size, extra_pages)


# -------------------------------------------------------------------------
# derandomized allocator cases (always run)
# -------------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(6))
def test_seeded_page_traffic(seed):
    rng = np.random.default_rng(seed)
    specs = [(tuple(int(t) for t in rng.integers(0, 3, rng.integers(1, 20))),
              int(rng.integers(1, 6))) for _ in range(10)]
    drive(specs, seed + 100, page_size=4, extra_pages=int(rng.integers(0, 8)))


def test_allocator_validation_and_capacity():
    with pytest.raises(ValueError):
        PageAllocator(1, 4)   # page 0 is reserved: need >= 2
    with pytest.raises(ValueError):
        PageAllocator(8, 0)
    a = PageAllocator(5, 4)   # 4 usable
    assert a.n_usable == 4 and a.n_free == 4
    assert a.pages_needed(5, 4) == 2    # positions [0, 8) at ps=4
    assert a.pages_needed(4, 1) == 1    # final sampled token never written
    table, n_shared = a.admit("A", [], 4)
    assert n_shared == 0 and a.n_free == 0
    with pytest.raises(RuntimeError):
        a.admit("B", [], 1)
    with pytest.raises(AssertionError):
        a.admit("A", [], 1)   # double admission of one owner
    assert sorted(a.release("A")) == sorted(table)
    assert a.n_free == 4


def test_cow_fork_shares_sealed_prefix_only():
    ps = 4
    a = PageAllocator(16, ps)
    p1 = tuple(range(10))                  # 2 full pages + tail
    k1 = prefix_page_keys(p1, ps, bucket=16)
    t1, s1 = a.admit("A", k1, a.pages_needed(10, 4))
    assert s1 == 0
    a.seal("A", k1)
    # same first 8 tokens, same bucket -> both full pages fork
    p2 = tuple(range(8)) + (9, 9)
    k2 = prefix_page_keys(p2, ps, bucket=16)
    t2, s2 = a.admit("B", k2, a.pages_needed(10, 4))
    assert s2 == 2 and t2[:2] == t1[:2], "full prefix pages must fork"
    assert not set(t2[2:]) & set(t1), "private suffixes must not alias"
    assert a.ref[t1[0]] == 2 and a.n_shared == 2
    assert a.cow_hits == 2
    # a different bucket must NOT fork (compiled-program provenance)
    k3 = prefix_page_keys(p2, ps, bucket=32)
    t3, s3 = a.admit("C", k3, a.pages_needed(10, 4))
    assert s3 == 0
    for o in ("A", "B", "C"):
        a.release(o)
    check_allocator(a)
    assert a.n_free == a.n_usable and not a.prefix_index


def test_preempt_retains_prefix_resume_is_fresh():
    ps = 4
    a = PageAllocator(16, ps)
    p1 = tuple(range(8))
    keys = prefix_page_keys(p1, ps, bucket=8)
    table, _ = a.admit("A", keys, a.pages_needed(8, 6))  # 4 pages
    a.seal("A", keys)
    prefix, private = a.private_suffix("A")
    assert prefix == table[:2] and private == table[2:]
    freed = a.detach_private("A")
    assert freed == private, "private suffix freed at preempt"
    assert a.retained["A"] == prefix and a.ref[prefix[0]] == 1
    # the sealed prefix stays in the COW index while retained
    assert len(a.lookup(keys)) == 2
    new_table = a.resume("A", len(private))
    assert new_table[:2] == prefix
    # physical ids may be reused (LIFO free list) but the pages are
    # exclusively ours again — the wipe restored the free-page invariant
    for p in new_table[2:]:
        assert a.ref[p] == 1
    a.release("A")
    check_allocator(a)
    assert not a.prefix_index, "last release must clear the COW index"


# -------------------------------------------------------------------------
# device pool: write masks, gather, placement
# -------------------------------------------------------------------------

def test_admit_pages_write_mask_protects_shared_pages():
    pool = PagedKVPool(CFG, 2, 32, page_size=8)
    rng = np.random.default_rng(0)
    p1 = rng.integers(1, CFG.vocab_size, 16).tolist()
    s1 = pool.alloc()
    n_sh, n_new, pages1, mask1 = pool.admit_pages(s1, "A", p1, 4, bucket=32)
    assert n_sh == 0
    assert mask1[:2].all(), "first tenant writes every full prompt page"
    assert not mask1[2:].any(), "padding past the prompt goes to trash"
    pool.seal_slot(s1)
    s2 = pool.alloc()
    p2 = p1 + [7, 8, 9]           # forks both full pages of p1
    n_sh, n_new, pages2, mask2 = pool.admit_pages(s2, "B", p2, 4, bucket=32)
    assert n_sh == 2
    assert not mask2[:2].any(), "COW-shared pages must never be rewritten"
    assert mask2[2], "the divergent page is private and written"
    assert list(pages2[:2]) == list(pages1[:2])
    assert not mask2[3:].any(), "bucket padding pages go to trash"


def test_gather_pages_reconstructs_table_order():
    leaf = jnp.arange(6 * 4 * 3).reshape(6, 4, 3).astype(jnp.float32)
    page_map = jnp.asarray([[3, 1, 0], [2, 2, 5]], jnp.int32)
    out = np.asarray(gather_pages(leaf, page_map))
    ref = np.asarray(leaf)[np.asarray(page_map).reshape(-1)].reshape(2, 12, 3)
    assert np.array_equal(out, ref)


def test_cache_spec_tree_paged_keeps_token_axis_unsharded():
    from repro.launch.mesh import make_mesh
    from repro.models.sharding import Sharder

    mesh = make_mesh((1, 1), ("data", "model"))
    sharder = Sharder(mesh, CFG, replicate_params_below=0)
    caches = lm.init_caches(CFG, 8, 4, per_slot=True)  # 8 pages of 4
    paged = sharder.cache_spec_tree(caches, 8, paged=True)
    flat = unsharded = 0
    for path, spec in jax.tree_util.tree_leaves_with_path(paged):
        keys = [getattr(k, "key", None) for k in path]
        if any(k in ("k", "v", "k_packed", "pos") for k in keys):
            assert spec.spec[2] is None, \
                f"paged token axis must stay unsharded: {keys} -> {spec.spec}"
            unsharded += 1
        flat += 1
    assert unsharded > 0


# -------------------------------------------------------------------------
# server integration: token identity + capacity win
# -------------------------------------------------------------------------

def _serve(params, cfg, prompts, *, paged, num_slots=3, max_new=6,
           n_pages=None, max_preemptions=0, priorities=None, seed=0,
           telemetry=None):
    srv = Server(params, cfg, num_slots=num_slots, max_seq_len=64, seed=seed,
                 paged=paged, page_size=8 if paged else 16, n_pages=n_pages,
                 max_preemptions=max_preemptions,
                 telemetry=telemetry if telemetry is not None else NOOP)
    for i, pr in enumerate(prompts):
        srv.submit(pr, max_new=max_new, arrival_time=float(i),
                   priority=0 if priorities is None else priorities[i])
    return srv, srv.run_until_drained()


@pytest.mark.parametrize("bits", [16, 8, 4])
def test_paged_tokens_identical_to_slot_pool(bits):
    cfg = CFG.with_kv_quant(bits) if bits < 16 else CFG
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, cfg.vocab_size, n).tolist()
               for n in (5, 11, 23, 7)]
    prompts.append(prompts[2][:16] + [3, 4, 5])   # shared-prefix fork
    _, ref = _serve(params, cfg, prompts, paged=False)
    srv, out = _serve(params, cfg, prompts, paged=True)
    assert out == ref, f"paged kv{bits} diverged from the slot pool"
    a = srv.pool.allocator
    assert a.n_free == a.n_usable and not a.ref, "pages leaked after drain"


def test_paged_preemption_token_identical():
    cfg = CFG.with_kv_quant(4)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, cfg.vocab_size, 12).tolist() for _ in range(3)]
    srv, out = _serve(params, cfg, prompts, paged=True, num_slots=2,
                      max_new=10, max_preemptions=2, priorities=[1, 1, 0])
    assert srv.scheduler.n_preemptions > 0, "scenario must actually preempt"
    # an unpressured paged run (enough slots, no preemption) is the oracle
    _, ref = _serve(params, cfg, prompts, paged=True, num_slots=3,
                    max_new=10)
    assert out == ref, "spill/restore of private pages changed tokens"
    a = srv.pool.allocator
    assert a.n_free == a.n_usable and not a.ref and not a.retained


def test_shared_prefix_capacity_win():
    """The tentpole's reason to exist: with a page budget far below
    num_slots * cache_len, shared-prefix requests are all resident at
    once because the prefix is stored ONCE — the same HBM in slot rows
    could not hold them."""
    cfg = CFG.with_kv_quant(4)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(3)
    base = rng.integers(1, cfg.vocab_size, 24).tolist()
    prompts = [base + rng.integers(1, cfg.vocab_size, 2).tolist()
               for _ in range(4)]
    # each request needs ceil((26 + 8 - 1)/8) = 5 pages worst case;
    # 4 unshared residents would need 20 — grant 12 (3 private + one
    # 3-page shared prefix each fits: 4*(5-3) + 3 = 11 <= 12)
    srv = Server(params, cfg, num_slots=4, max_seq_len=64, seed=0,
                 paged=True, page_size=8, n_pages=13)
    for pr in prompts:
        srv.submit(pr, max_new=8, arrival_time=0.0)
    peak = 0
    while not srv.scheduler.drained:
        srv.step()
        peak = max(peak, len(srv.scheduler.running))
    assert peak == 4, f"COW should hold all 4 residents, peak={peak}"
    assert srv.pool.allocator.cow_hits >= 9, "prefix pages must fork"
    res = {r.id: list(r.tokens) for r in srv.scheduler.finished}
    _, ref = _serve(params, cfg, prompts, paged=False, num_slots=4,
                    max_new=8)
    assert res == ref, "the shared-prefix residents must still decode " \
        "token-identically to unshared slot rows"


def test_paged_trace_and_gauges():
    cfg = CFG.with_kv_quant(4)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(1, cfg.vocab_size, 12).tolist() for _ in range(3)]
    tel = Telemetry()
    srv, out = _serve(params, cfg, prompts, paged=True, num_slots=2,
                      max_new=8, max_preemptions=1, priorities=[1, 1, 0],
                      telemetry=tel)
    stats = validate_events(tel.tracer.events)
    assert stats["requests"] == 3
    names = {e["name"] for e in tel.tracer.events}
    assert {"page_alloc", "page_release"} <= names
    reg = tel.registry
    assert reg.gauge("kv_pages_total").value == srv.pool.allocator.n_usable
    assert reg.gauge("kv_pages_free").value == srv.pool.allocator.n_free
    assert reg.counter("kv_pages_alloc_total").value > 0
    assert reg.counter("kv_pages_freed_total").value \
        == reg.counter("kv_pages_alloc_total").value, \
        "drained serve must free every allocated page"


def test_page_remap_sweep_compiles_once_per_bucket():
    """Auditor-backed recompile regression (analysis.audit.compile_count):
    the page table rides as a traced argument, so a sweep of staggered
    admissions, retires, and preemptions — the tables remapping at every
    slot turnover — must reuse ONE compiled decode step, and prefill
    must compile exactly once per length bucket."""
    cfg = CFG.with_kv_quant(4)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(6)
    # buckets: 9..12 -> 16, 5/7 -> 8; slot churn guarantees fresh tables
    lens = (9, 12, 5, 10, 7, 11)
    prompts = [rng.integers(1, cfg.vocab_size, n).tolist() for n in lens]
    srv, out = _serve(params, cfg, prompts, paged=True, num_slots=2,
                      max_new=6, max_preemptions=1,
                      priorities=[1, 1, 0, 0, 1, 0])
    assert all(len(t) == 6 for t in out.values())
    assert srv.scheduler.n_preemptions > 0, "sweep must exercise a remap " \
        "via spill/restore, not just slot turnover"
    n_step = compile_count(srv._step_paged)
    if n_step is not None:  # jax>=0.4 exposes the compile-cache size
        assert n_step == 1, f"page remaps recompiled decode: {n_step}"
        n_pf = compile_count(srv._prefill_paged)
        assert n_pf == 2, f"2 buckets must mean 2 compiled prefills, " \
            f"got {n_pf}"


def test_paged_flag_validation():
    params = lm.init_params(jax.random.PRNGKey(0), CFG)
    with pytest.raises(ValueError, match="n_pages requires"):
        Server(params, CFG, num_slots=2, max_seq_len=32, n_pages=8)
    with pytest.raises(ValueError, match="mutually exclusive"):
        Server(params, CFG, num_slots=2, max_seq_len=32, paged=True,
               prefill_chunk=8)
    with pytest.raises(ValueError):
        PagedKVPool(CFG, 2, 32, page_size=6)     # not a power of two
    with pytest.raises(ValueError):
        PagedKVPool(CFG, 2, 36, page_size=8)     # must divide cache_len
    ssm = get_arch("mamba2-130m").reduced()
    sparams = lm.init_params(jax.random.PRNGKey(0), ssm)
    with pytest.raises(ValueError, match="full attention"):
        Server(sparams, ssm, num_slots=2, max_seq_len=32, paged=True)


def test_submit_budget_boundary():
    """Satellite audit: positions [0, L + max_new - 1) are written, so a
    request with L + max_new - 1 == cache_len fits exactly (the old
    bound rejected it) and one more token is over budget."""
    params = lm.init_params(jax.random.PRNGKey(0), CFG)
    for paged in (False, True):
        srv = Server(params, CFG, num_slots=1, max_seq_len=16, paged=paged,
                     page_size=8)
        rid = srv.submit(list(range(1, 9)), max_new=9)   # 8 + 9 - 1 == 16
        out = srv.run_until_drained()
        assert len(out[rid]) == 9, "boundary request must serve in full"
        with pytest.raises(ValueError, match="cache positions"):
            srv.submit(list(range(1, 9)), max_new=10)
