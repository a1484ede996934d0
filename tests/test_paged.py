"""Paged KV cache + chunked-prefill scheduler tests.

Conformance: the paged engine must match the dense engine bit-exactly —
same sampled tokens and identical per-slot cycle totals under mixed
QuantPolicies (the ``rc.kv_layout`` A/B of DESIGN.md §8) — plus block-table
allocator invariants (hypothesis), length-masked int8 reads, recompute
preemption, and scheduler-vs-legacy greedy agreement."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs.base import RunConfig, get_config
from repro.models import KVView, init
from repro.models.attention import init_kv_cache, kv_cache_read, kv_cache_write
from repro.serve import Engine, Request, Scheduler
from repro.serve.cache import BlockManager

RC = RunConfig(
    dtype="float32", param_dtype="float32", remat="none",
    prefill_chunk=5, kv_cache_dtype="int8",
)


def _run_sched(cfg, rc, params, *, prompts, max_new=4, max_batch=3,
               capacity=32, **kw):
    s = Scheduler(cfg, rc, params, capacity=capacity, max_batch=max_batch, **kw)
    for rid, p in enumerate(prompts):
        s.submit(Request(rid=rid, prompt=list(p), max_new=max_new))
    done = s.run()
    return s, {r.rid: r.out for r in done}


# ------------------------------------------------------------ A/B conformance
@pytest.mark.parametrize(
    "arch,policy",
    [
        ("qwen3-0.6b_smoke", "attn.*=int8,*=int2"),
        ("deepseek-v2-lite-16b_smoke", "mla.*=int8,*=int2"),
    ],
)
def test_paged_matches_dense_tokens_and_cycles(arch, policy):
    """kv_layout A/B: identical sampled tokens (temperature>0 — any logit
    bit-flip would change the categorical draw) and *identical* per-slot
    cycle totals at a mixed int8/int2 policy (the tuGEMM cycle counts are
    data-dependent, so this also certifies every GEMM saw identical
    activations through both cache layouts)."""
    cfg = get_config(arch)
    rc = dataclasses.replace(RC, quant_policy=policy)
    params = init(cfg, rc, jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, 4 + 3 * i).tolist() for i in range(5)]

    kw = dict(prompts=prompts, track_energy=True, temperature=0.7, seed=3)
    s_d, out_d = _run_sched(cfg, rc, params, **kw)
    rc_p = dataclasses.replace(rc, kv_layout="paged", block_size=4)
    s_p, out_p = _run_sched(cfg, rc_p, params, **kw)

    assert out_d == out_p
    cyc_d = {e["rid"]: e["cycles_by_bits"] for e in s_d.energy_summary()}
    cyc_p = {e["rid"]: e["cycles_by_bits"] for e in s_p.energy_summary()}
    assert cyc_d == cyc_p
    assert all(sum(v.values()) > 0 for v in cyc_d.values())
    assert {2, 8} <= set(next(iter(cyc_d.values())))  # both widths metered
    s_p.mgr.check_invariants()


def test_mixed_step_logits_bitexact_dense_vs_paged():
    """Unit-level A/B of one mixed prefill+decode step: same rows (one
    prefill chunk, one decode, one idle), bitwise-equal logits."""
    from repro.serve.scheduler import build_mixed_step

    cfg = get_config("qwen3-0.6b_smoke")
    params = init(cfg, RC, jax.random.PRNGKey(2))
    capacity, bs = 16, 4
    from repro.models import init_caches

    tokens = jnp.asarray(np.random.default_rng(0).integers(0, cfg.vocab_size, (3, 5)),
                         jnp.int32)
    pos = jnp.asarray([3, 7, 0], jnp.int32)   # row2 idle
    lens = jnp.asarray([5, 1, 0], jnp.int32)

    rc_d = RC
    caches_d = init_caches(cfg, rc_d, 3, capacity)
    # pre-populate rows 0/1 so the step extends real history, not zeros
    warm = jnp.asarray(np.random.default_rng(3).integers(0, cfg.vocab_size, (3, 7)),
                       jnp.int32)
    step_d = build_mixed_step(cfg, rc_d)
    caches_d, _ = step_d(params, caches_d, warm,
                         jnp.zeros(3, jnp.int32), jnp.asarray([3, 7, 0], jnp.int32), None)
    _, logits_d = step_d(params, caches_d, tokens, pos, lens, None)

    rc_p = dataclasses.replace(RC, kv_layout="paged", block_size=bs)
    mgr = BlockManager(3 * capacity // bs, bs, 3, capacity)
    assert mgr.extend(0, 8) and mgr.extend(1, 8)
    caches_p = init_caches(cfg, rc_p, 3, capacity)
    step_p = build_mixed_step(cfg, rc_p)
    tables = jnp.asarray(mgr.tables)
    caches_p, _ = step_p(params, caches_p, warm,
                         jnp.zeros(3, jnp.int32), jnp.asarray([3, 7, 0], jnp.int32), tables)
    _, logits_p = step_p(params, caches_p, tokens, pos, lens, tables)

    assert np.array_equal(np.asarray(logits_d), np.asarray(logits_p))


def test_scheduler_matches_legacy_engine_greedy():
    """Same-length prompts admitted together: the scheduler's greedy output
    equals the legacy engine's (the legacy shared-position counter is only
    correct in exactly this regime — the scheduler generalizes it)."""
    cfg = get_config("qwen3-0.6b_smoke")
    rc = dataclasses.replace(RC, prefill_chunk=8)  # one chunk covers the prompt
    params = init(cfg, rc, jax.random.PRNGKey(4))
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, 6).tolist() for _ in range(3)]

    eng = Engine(cfg, rc, params, capacity=32, max_batch=3)
    for rid, p in enumerate(prompts):
        eng.submit(Request(rid=rid, prompt=list(p), max_new=5))
    eng.run()
    out_legacy = {r.rid: r.out for r in eng.slots if r is not None}

    _, out_sched = _run_sched(cfg, rc, params, prompts=prompts, max_new=5)
    assert out_sched == out_legacy


# --------------------------------------------------------- length-masked read
def _stacked(cache: dict, layers: int) -> dict:
    """A layer group's cache: each buffer stacked along a leading layers axis."""
    return {n: jnp.stack([b] * layers) for n, b in cache.items()}


def test_dense_int8_read_masks_stale_tail():
    """Slot reuse: positions at/beyond kv_len dequantize to exact zeros even
    when the buffer still holds a previous occupant's quantized tokens."""
    cfg = get_config("qwen3-0.6b_smoke")
    cache = _stacked(init_kv_cache(cfg, 2, 8, jnp.int8), 2)
    rng = np.random.default_rng(0)
    full = jnp.asarray(rng.normal(size=(2, 8, cfg.num_kv_heads, cfg.resolved_head_dim)),
                       jnp.float32)
    cache = kv_cache_write(cache, ("k",), (full,), 0, layer=1)  # old occupant: 8 tokens
    kv_len = jnp.asarray([3, 5], jnp.int32)               # new occupants shorter
    out = kv_cache_read(cache, "k", jnp.float32, kv_len=kv_len, layer=1)
    assert np.abs(np.asarray(out[0, :3])).sum() > 0
    assert np.asarray(out[0, 3:]).sum() == 0.0
    assert np.asarray(out[1, 5:]).sum() == 0.0


def test_paged_write_read_matches_dense():
    """Tokens scattered through a block table read back identical to the
    dense layout at every live position (int8: same per-token scales)."""
    cfg = get_config("qwen3-0.6b_smoke")
    capacity, bs, B = 12, 4, 2
    rng = np.random.default_rng(7)
    kv = jnp.asarray(rng.normal(size=(B, 6, cfg.num_kv_heads, cfg.resolved_head_dim)),
                     jnp.float32)
    pos = jnp.asarray([0, 2], jnp.int32)
    lens = jnp.asarray([6, 3], jnp.int32)

    dense = _stacked(init_kv_cache(cfg, B, capacity, jnp.int8), 2)
    view_d = KVView(pos=pos, lens=lens, tables=None, block_size=bs, layout="dense",
                    layer=1)
    dense = kv_cache_write(dense, ("k",), (kv,), None, view=view_d)
    out_d = kv_cache_read(dense, "k", jnp.float32, kv_len=pos + lens, layer=1)

    mgr = BlockManager(B * capacity // bs, bs, B, capacity)
    assert mgr.extend(0, 6) and mgr.extend(1, 5)
    pool = _stacked(init_kv_cache(cfg, mgr.num_pages + 1, bs, jnp.int8, paged=True), 2)
    view_p = KVView(pos=pos, lens=lens, tables=jnp.asarray(mgr.tables),
                    block_size=bs, layout="paged", layer=1)
    pool = kv_cache_write(pool, ("k",), (kv,), None, view=view_p)
    out_p = kv_cache_read(pool, "k", jnp.float32, kv_len=pos + lens, view=view_p)

    # the paged pool stores heads head-major on one feature axis
    assert np.array_equal(np.asarray(out_d).reshape(out_p.shape), np.asarray(out_p))


# ----------------------------------------------------------------- allocator
@settings(max_examples=60, deadline=None)
@given(
    st.integers(2, 6),     # block_size
    st.integers(2, 5),     # slots
    st.integers(1, 10),    # pool pages
    st.lists(
        st.tuples(st.integers(0, 4), st.integers(0, 3), st.integers(1, 7)),
        min_size=1, max_size=40,
    ),
)
def test_block_manager_invariants(bs, slots, pages, ops):
    """Random allocate/extend/truncate/release interleavings (the full
    submit/append/rollback/free alphabet speculative decoding exercises):
    free list ⊎ allocated pages always partition the pool, no slot's table
    references a freed page, peak pages ≤ pool, failed extends leave state
    intact, and truncation frees exactly the pages past the new length."""
    capacity = bs * 6
    mgr = BlockManager(pages, bs, slots, capacity)
    lens = [0] * slots
    for slot, op, amount in ops:
        slot %= slots
        if op == 0:  # extend by `amount` tokens (capped at table capacity)
            new_len = min(lens[slot] + amount, mgr.max_blocks * bs)
            before = (mgr.pages_in_use, mgr.blocks_of(slot))
            if mgr.extend(slot, new_len):
                lens[slot] = new_len
            else:  # failed extend must not mutate
                assert (mgr.pages_in_use, mgr.blocks_of(slot)) == before
        elif op == 1:
            mgr.release(slot)
            lens[slot] = 0
        elif op == 2:  # refill: release then immediately re-extend
            mgr.release(slot)
            lens[slot] = 0
            if mgr.extend(slot, min(amount, mgr.max_blocks * bs)):
                lens[slot] = min(amount, mgr.max_blocks * bs)
        else:  # speculative rollback: shrink by `amount` tokens
            new_len = max(lens[slot] - amount, 0)
            kept = mgr.blocks_of(slot)[: -(-new_len // bs)] if new_len else []
            mgr.truncate(slot, new_len)
            lens[slot] = new_len
            # the surviving prefix keeps its pages, in order
            assert mgr.blocks_of(slot) == kept
        mgr.check_invariants()
        assert mgr.high_water <= mgr.num_pages
        # every slot backed by enough pages for its length
        for s in range(slots):
            assert len(mgr.blocks_of(s)) * bs >= lens[s]


def test_block_manager_truncate_unit():
    """Rollback frees exactly the pages past the new high block, reuses them
    LIFO, and refuses to grow."""
    mgr = BlockManager(6, 4, 2, 24)
    assert mgr.extend(0, 10)                   # 3 pages
    p0 = mgr.blocks_of(0)
    mgr.truncate(0, 5)                         # ceil(5/4)=2 pages survive
    assert mgr.blocks_of(0) == p0[:2]
    assert mgr.pages_in_use == 2
    assert p0[2] in mgr.free
    with pytest.raises(ValueError):
        mgr.truncate(0, 6)                     # rollback cannot grow
    assert mgr.extend(0, 12)                   # freed page comes back first
    assert mgr.blocks_of(0) == p0
    mgr.truncate(0, 0)                         # full rollback
    assert mgr.blocks_of(0) == [] and mgr.pages_in_use == 0
    mgr.check_invariants()


# ----------------------------------------------------------------- scheduler
def test_scheduler_preemption_under_pool_pressure():
    """A pool far smaller than max_batch×capacity still drains every
    request via recompute preemption, and the high-water mark stays ≤ pool."""
    cfg = get_config("qwen3-0.6b_smoke")
    rc = dataclasses.replace(RC, prefill_chunk=4, kv_layout="paged", block_size=4)
    params = init(cfg, rc, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, 10).tolist() for _ in range(6)]
    s, out = _run_sched(cfg, rc, params, prompts=prompts, max_new=8,
                        num_pages=10, capacity=32)
    s.mgr.check_invariants()
    assert sorted(out) == list(range(6))
    assert all(len(v) == 8 for v in out.values())
    assert s.preemptions > 0
    assert s.mgr.high_water <= 10


def test_scheduler_single_compile_across_ticks():
    """Every tick reuses one compiled mixed step regardless of the
    prefill/decode mix (the legacy engine compiled per prompt length)."""
    cfg = get_config("qwen3-0.6b_smoke")
    params = init(cfg, RC, jax.random.PRNGKey(1))
    s = Scheduler(cfg, RC, params, capacity=32, max_batch=2)
    rng = np.random.default_rng(2)
    for rid, plen in enumerate([3, 7, 11, 6]):  # varied prompt lengths
        s.submit(Request(rid=rid, prompt=rng.integers(0, cfg.vocab_size, plen).tolist(),
                         max_new=3))
    s.run()
    if hasattr(s._step, "_cache_size"):
        # width-adaptive ticks: one entry for mixed (chunk-wide) ticks, one
        # for decode-only width-1 ticks — O(1) regardless of prompt lengths
        assert s._step._cache_size() <= 2
    assert len(s.finished) == 4


def test_scheduler_rejects_ssm():
    cfg = get_config("falcon-mamba-7b_smoke")
    with pytest.raises(NotImplementedError):
        Scheduler(cfg, RC, params={}, capacity=16, max_batch=1)


def test_legacy_engine_rejects_paged_layout():
    cfg = get_config("qwen3-0.6b_smoke")
    rc = dataclasses.replace(RC, kv_layout="paged")
    with pytest.raises(ValueError):
        Engine(cfg, rc, params={}, capacity=16, max_batch=1)


def test_tight_token_budget_round_robins_decodes():
    """token_budget=1 with two rows already decoding: the rotating plan
    order alternates them tick by tick instead of draining slot 0 to
    completion first (decode rows keep absolute priority over prefill, so
    the scarce-budget fairness must come from the rotation)."""
    from repro.serve.scheduler import _Slot

    cfg = get_config("qwen3-0.6b_smoke")
    rc = dataclasses.replace(RC, prefill_chunk=4, token_budget=1)
    params = init(cfg, rc, jax.random.PRNGKey(6))
    s = Scheduler(cfg, rc, params, capacity=32, max_batch=2)
    # both slots mid-decode (prompt fully in cache, one token sampled)
    for i in range(2):
        s.slots[i] = _Slot(req=Request(rid=i, prompt=[1 + i, 2, 3], max_new=6,
                                       out=[7]),
                           prompt=[1 + i, 2, 3], admit_seq=i, pos=3, last_token=7)
    spread = []
    for _ in range(30):
        if not s.tick():
            break
        outs = {r.rid: len(r.out) for r in s.finished}
        for sl in s.slots:
            if sl is not None:
                outs[sl.req.rid] = len(sl.req.out)
        spread.append(abs(outs[0] - outs[1]))
    assert len(s.finished) == 2
    # round-robin keeps the two within one token of each other at every
    # tick; index-priority scheduling would push the spread to max_new
    assert max(spread) <= 1, spread


def test_scheduler_max_new_one_finishes_at_prefill():
    """The prefill-sampled token counts toward max_new (legacy semantics):
    a max_new=1 request never occupies a decode row."""
    cfg = get_config("qwen3-0.6b_smoke")
    params = init(cfg, RC, jax.random.PRNGKey(5))
    s, out = _run_sched(cfg, RC, params, prompts=[[1, 2, 3]], max_new=1)
    assert out == {0: out[0]} and len(out[0]) == 1
    assert s.generated_tokens == 1


# ------------------------------------------------- prefix cache (DESIGN.md §11)
def test_block_manager_cow_unit():
    """Copy-on-write mechanics: a write into a page another slot still
    references retables the writer onto a fresh page, queues exactly one
    (src, dst) device copy, and transfers one refcount — the shared page is
    never mutated while anyone else holds it."""
    mgr = BlockManager(8, 4, 2, 16, prefix_cache=True)
    assert mgr.extend(0, 9)
    seq = list(range(9))
    mgr.register_prefix(0, seq, now=0)
    nodes, matched = mgr.lookup_prefix(seq, now=1)
    assert matched == 8                        # (9-1)//4 = 2 full blocks
    assert mgr.fork_prefix(1, nodes, now=1) == 8
    shared = mgr.blocks_of(0)[:2]
    assert mgr.blocks_of(1) == shared
    assert all(int(mgr.refcounts[p]) == 2 for p in shared)
    mgr.check_invariants()

    # roll the fork back INTO the shared region, then write: COW must fire
    mgr.truncate(1, 7)
    assert mgr.blocks_of(1) == shared          # truncate drops refs, not these
    assert mgr.extend(1, 8)
    assert mgr.cow_events == 1
    copies = mgr.drain_cow_copies()
    assert len(copies) == 1 and copies[0][0] == shared[1]
    assert mgr.blocks_of(1)[1] == copies[0][1] != shared[1]
    assert int(mgr.refcounts[shared[1]]) == 1  # back to slot 0 alone
    mgr.check_invariants()

    # rewriting an exclusively-owned *registered* page drops its trie
    # subtree (the content is about to diverge from the indexed tokens)
    before = len(mgr.prefix)
    mgr.truncate(0, 7)
    assert mgr.extend(0, 8)
    assert mgr.cow_events == 1                 # rc was 1: no copy needed
    assert len(mgr.prefix) < before
    mgr.check_invariants()


def test_block_manager_cached_prefix_retention_and_eviction():
    """Release of the last reference keeps trie-indexed pages allocated as
    refcount-0 cached prefixes; pool pressure evicts them LRU (leaves
    first) inside extend, strictly before the call could report failure."""
    mgr = BlockManager(4, 4, 2, 16, prefix_cache=True)
    assert mgr.extend(0, 8)
    mgr.register_prefix(0, list(range(8)), now=0)
    mgr.release(0)
    assert mgr.pages_in_use == 2 and mgr.cached_pages == 2
    assert mgr.live_pages == 0
    mgr.check_invariants()

    # a fork revives the cached chain (refcount 0 -> 1, no allocation)
    nodes, matched = mgr.lookup_prefix(list(range(8)) + [9], now=1)
    assert matched == 8
    mgr.fork_prefix(1, nodes, now=1)
    assert mgr.cached_pages == 0 and mgr.live_pages == 2
    mgr.release(1)
    assert mgr.cached_pages == 2

    # pool pressure: a 4-block extend on the 4-page pool must evict both
    # cached pages rather than fail
    assert mgr.extend(1, 16)
    assert mgr.prefix.evictions == 2 and len(mgr.prefix) == 0
    mgr.check_invariants()


def test_block_manager_lru_evicts_leaves_before_parents():
    """Eviction victims are childless cached nodes (deepest first), oldest
    last_used first — a chain never dangles."""
    mgr = BlockManager(3, 4, 2, 16, prefix_cache=True)
    assert mgr.extend(0, 12)
    mgr.register_prefix(0, list(range(12)), now=5)
    mgr.release(0)
    chain = [n.page for n in mgr.prefix.walk(list(range(12)), 3, now=5)]
    assert len(chain) == 3
    # evict one page: must be the deepest (only childless) node
    assert mgr.extend(1, 4)
    assert mgr.prefix.evictions == 1
    assert chain[2] not in mgr.prefix.node_of_page
    assert chain[0] in mgr.prefix.node_of_page
    mgr.check_invariants()


@settings(deadline=None, max_examples=60)
@given(
    st.integers(0, 2 ** 31 - 1),
    st.lists(
        st.tuples(st.integers(0, 3), st.integers(0, 4), st.integers(1, 9)),
        min_size=1, max_size=50,
    ),
)
def test_block_manager_refcount_invariants(seed, ops):
    """Random interleavings of the full prefix-sharing alphabet — extend,
    release, rollback, register, lookup+fork — preserve the generalized
    partition (live ⊎ cached ⊎ free == pool, Σ table references ==
    refcounts) and the COW guarantee: after any successful extend, every
    page in the slot's write range is exclusively owned (refcount 1) —
    shared pages are copied, never mutated in place."""
    bs, slots = 4, 3
    rng = np.random.default_rng(seed)
    mgr = BlockManager(10, bs, slots, bs * 5, prefix_cache=True)
    lens = [0] * slots
    # per-slot token sequences from a tiny alphabet, so prefixes collide
    # across slots and the trie genuinely shares
    seqs = [[] for _ in range(slots)]
    for slot, op, amount in ops:
        slot %= slots
        if op == 0:  # extend + commit `amount` tokens
            new_len = min(lens[slot] + amount, mgr.max_blocks * bs)
            start_blk = lens[slot] // bs
            snap = (mgr.pages_in_use, mgr.blocks_of(slot),
                    mgr.refcounts.copy().tolist())
            if mgr.extend(slot, new_len):
                while len(seqs[slot]) < new_len:
                    seqs[slot].append(int(rng.integers(0, 3)))
                lens[slot] = new_len
                for b in range(start_blk, -(-new_len // bs)):
                    p = int(mgr.tables[slot, b])
                    assert int(mgr.refcounts[p]) == 1, (
                        "write range page shared after extend")
            else:
                assert (mgr.pages_in_use, mgr.blocks_of(slot),
                        mgr.refcounts.copy().tolist()) == snap
        elif op == 1:
            mgr.release(slot)
            lens[slot], seqs[slot] = 0, []
        elif op == 2:  # speculative rollback
            new_len = max(lens[slot] - amount, 0)
            mgr.truncate(slot, new_len)
            lens[slot] = new_len
            seqs[slot] = seqs[slot][:new_len]
        elif op == 3:  # index committed full blocks
            mgr.register_prefix(slot, seqs[slot][: lens[slot]], now=amount)
        else:  # lookup + fork onto an empty slot
            probe = seqs[slot][: lens[slot]] + [int(rng.integers(0, 3))]
            nodes, matched = mgr.lookup_prefix(probe, now=amount)
            dst = (slot + 1) % slots
            if nodes and lens[dst] == 0 and int(mgr.blocks_used[dst]) == 0:
                assert mgr.fork_prefix(dst, nodes, now=amount) == matched
                lens[dst] = matched
                seqs[dst] = probe[:matched]
        mgr.check_invariants()
        for s in range(slots):
            assert len(mgr.blocks_of(s)) * bs >= lens[s]


def _run_sequential(cfg, rc, params, prompts, max_new=4):
    """One request at a time on a 1-slot scheduler: decode-tick composition
    is identical with the prefix cache on or off, so per-slot cycle totals
    must match bit-for-bit except the skipped prefill chunks."""
    s = Scheduler(cfg, rc, params, capacity=32, max_batch=1, track_energy=True)
    for rid, p in enumerate(prompts):
        s.submit(Request(rid=rid, prompt=list(p), max_new=max_new))
        s.run()
    return s, {r.rid: r.out for r in s.finished}


def test_prefix_cache_bitexact_and_zero_cycle_reuse():
    """Tentpole acceptance (sequential trace): with the prefix cache on, a
    second request sharing the first's prompt prefix emits identical
    tokens, the first request's cycle totals are bit-identical to the
    uncached run, and the second's prefill cycles drop — the matched
    prefix is charged ZERO cycles, recorded explicitly in
    ``SlotMeter.cached_prompt_tokens``."""
    cfg = get_config("qwen3-0.6b_smoke")
    rc = dataclasses.replace(RC, quant_policy="attn.*=int8,*=int2",
                             kv_layout="paged", block_size=4)
    params = init(cfg, rc, jax.random.PRNGKey(0))
    rng = np.random.default_rng(7)
    shared = rng.integers(0, cfg.vocab_size, 13).tolist()
    prompts = [shared + rng.integers(0, cfg.vocab_size, 3 + i).tolist()
               for i in range(2)]

    s_off, out_off = _run_sequential(cfg, rc, params, prompts)
    rc_on = dataclasses.replace(rc, prefix_cache=True)
    s_on, out_on = _run_sequential(cfg, rc_on, params, prompts)

    assert out_off == out_on
    cyc_off = {e["rid"]: e["cycles_by_bits"] for e in s_off.energy_summary()}
    cyc_on = {e["rid"]: e["cycles_by_bits"] for e in s_on.energy_summary()}
    # request 0 never matched anything: identical down to the last cycle
    assert cyc_off[0] == cyc_on[0]
    # request 1 skipped 3 blocks of prefill: strictly cheaper at every width
    assert all(cyc_on[1][b] < cyc_off[1][b] for b in cyc_off[1])
    meters = {m.rid: m for m in s_on.finished_meters}
    assert meters[1].cached_prompt_tokens == 12   # 3 blocks of 4
    assert meters[0].cached_prompt_tokens == 0
    assert s_on.prefix_hits == 1 and s_on.prefix_tokens_reused == 12
    s_on.mgr.check_invariants()
    # drained: no live pages, only cached prefixes remain allocated
    assert s_on.mgr.live_pages == 0
    assert s_on.mgr.pages_in_use == s_on.mgr.cached_pages > 0


def test_prefix_cache_concurrent_shared_prompt():
    """Concurrent shared-prompt trace (one warm request, then a burst):
    identical greedy tokens, fewer prefill tokens computed, and a lower
    live-page high-water — the shared prefix occupies ONE set of pages."""
    cfg = get_config("qwen3-0.6b_smoke")
    rc = dataclasses.replace(RC, quant_policy="*=int8",
                             kv_layout="paged", block_size=4)
    params = init(cfg, rc, jax.random.PRNGKey(0))
    rng = np.random.default_rng(8)
    shared = rng.integers(0, cfg.vocab_size, 17).tolist()
    burst = [shared + rng.integers(0, cfg.vocab_size, 2 + i).tolist()
             for i in range(4)]

    def run(rc_):
        s = Scheduler(cfg, rc_, params, capacity=32, max_batch=3)
        s.submit(Request(rid=0, prompt=list(shared) + [1, 2, 3], max_new=4))
        s.run()                       # warm: registers the shared blocks
        for rid, p in enumerate(burst, start=1):
            s.submit(Request(rid=rid, prompt=list(p), max_new=4))
        s.run()
        return s, {r.rid: r.out for r in s.finished}

    s_off, out_off = run(rc)
    s_on, out_on = run(dataclasses.replace(rc, prefix_cache=True))
    assert out_off == out_on
    assert s_on.prefix_hits == 4      # every burst request forked the prefix
    assert s_on.prefix_tokens_reused == 4 * 16
    # >= 2x reduction in prefill tokens actually computed for the burst
    assert s_on.prefill_tokens_computed * 2 <= s_off.prefill_tokens_computed
    assert s_on.mgr.live_high_water < s_off.mgr.live_high_water
    s_on.mgr.check_invariants()
    assert s_on.mgr.live_pages == 0   # drained; cached prefixes remain


def test_prefix_cache_with_speculative_decode():
    """Composition: prefix forking + int2 speculative drafting still emit
    exactly the plain non-speculative uncached tokens (greedy), and the
    shared BlockManager's refcount invariants survive fork/rollback."""
    cfg = get_config("qwen3-0.6b_smoke")
    rc = dataclasses.replace(RC, quant_policy="*=int8",
                             kv_layout="paged", block_size=4)
    params = init(cfg, rc, jax.random.PRNGKey(0))
    rng = np.random.default_rng(9)
    shared = rng.integers(0, cfg.vocab_size, 9).tolist()
    prompts = [shared + rng.integers(0, cfg.vocab_size, 2 + i).tolist()
               for i in range(3)]

    s_plain, out_plain = _run_sequential(cfg, rc, params, prompts, max_new=5)
    rc_spec = dataclasses.replace(rc, prefix_cache=True, spec_gamma=2,
                                  draft_policy="*=int2")
    s_spec, out_spec = _run_sequential(cfg, rc_spec, params, prompts, max_new=5)
    assert out_plain == out_spec
    assert s_spec.prefix_hits == 2
    s_spec.mgr.check_invariants()


def test_scheduler_cow_device_copy():
    """The scheduler's COW drain really copies the page in BOTH device pools
    (target + draft) before the next write: after a forced COW, the fresh
    page's contents equal the shared source page bit-for-bit."""
    cfg = get_config("qwen3-0.6b_smoke")
    rc = dataclasses.replace(RC, quant_policy="*=int8",
                             kv_layout="paged", block_size=4,
                             prefix_cache=True)
    params = init(cfg, rc, jax.random.PRNGKey(0))
    s = Scheduler(cfg, rc, params, capacity=32, max_batch=2)
    rng = np.random.default_rng(10)
    s.submit(Request(rid=0, prompt=rng.integers(0, cfg.vocab_size, 9).tolist(),
                     max_new=2))
    s.run()
    # fork the registered prefix onto slot 0, then force a write into the
    # shared second block (the engine never does this on its own — COW is
    # the manager's defense in depth, so drive it through the public API)
    seq = s.finished[0].prompt + s.finished[0].out
    nodes, matched = s.mgr.lookup_prefix(seq, now=99)
    assert matched >= 8
    s.mgr.fork_prefix(0, nodes[:2], now=99)
    s.mgr.fork_prefix(1, nodes[:2], now=99)
    s.mgr.truncate(0, 7)
    assert s.mgr.extend(0, 8)
    assert s.mgr.cow_events == 1
    src, dst = s.mgr.cow_copies[0]
    s._drain_cow()
    for leaf in jax.tree.leaves(s.caches):
        np.testing.assert_array_equal(np.asarray(leaf[:, src]),
                                      np.asarray(leaf[:, dst]))
    s.mgr.check_invariants()
