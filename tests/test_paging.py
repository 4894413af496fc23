"""Paged slot-cache acceptance (ISSUE 4 / DESIGN.md §8).

Three layers of assurance, cheapest first:

* *bookkeeping invariants*, fuzzed without any model compute: every page is
  free, uniquely owned, or the trash block; eviction zeroes pages and
  returns them; the block table and free lists never desync;
* *round-trip*: ``paged_insert`` → ``paged_read`` recovers the prefill
  cache for every family, and evicted pages come back zeroed;
* *the headline invariant*, property-fuzzed through the real engine: for
  randomized admission/eviction/length schedules, page sizes, and page
  budgets — including budgets tight enough to force decode-time
  ``PoolExhausted`` preemptions — the paged engine's token streams are
  **bit-identical** to the sequential per-request ``generate()`` baseline
  for dense, SSM, and hybrid families with SC-GEMM on.

Fuzzing goes through ``tests/_propcheck.py``: hypothesis when installed,
deterministic fixed-seed sweeps otherwise. The deep sweep is gated behind
``pytest -m slow`` (the scheduled CI job runs it).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _propcheck import given, settings, st
from repro.configs.base import ModelConfig
from repro.launch.serve import generate
from repro.models import bind
from repro.serving import (Engine, PagedSlotPool, PoolExhausted, PrefixCache,
                           PrefixCacheInvariantError, Request, SlotEntry,
                           SlotPool)


def _cfg(family, **kw):
    base = dict(name=f"pg-{family}", family=family, n_layers=2, d_model=64,
                n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128, vocab_size=128,
                dtype="float32", q_block=16, kv_block=16, loss_chunk=16,
                remat=False, use_sc_gemm=True)
    base.update(kw)
    return ModelConfig(**base).validate()


CASES = [
    _cfg("dense"),
    _cfg("ssm", n_kv_heads=1, d_ff=0, ssm_state=16, ssm_headdim=16,
         ssm_chunk=4),
    _cfg("hybrid", n_kv_heads=4, ssm_state=16, ssm_headdim=16, ssm_chunk=4,
         shared_attn_every=2, n_layers=4),
]


@pytest.fixture(scope="module")
def dense_params():
    return bind(CASES[0]).init_params(jax.random.PRNGKey(0))


def _params(cfg):
    return bind(cfg).init_params(jax.random.PRNGKey(0))


def _prompt(cfg, s, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab_size, size=(s,)).astype(np.int32)


def _fake_single(m, prompt_len):
    """A synthetic B=1 'prefill' cache (all-ones leaves, pos=prompt_len):
    enough for bookkeeping/round-trip tests without running the model."""
    single = m.init_cache(1, prompt_len)
    single = jax.tree.map(jnp.ones_like, single)
    return single._replace(pos=jnp.full((1,), prompt_len, jnp.int32))


def _entry(uid, prompt_len=4, gen=2):
    return SlotEntry(request=Request(uid=uid,
                                     prompt=np.ones(prompt_len, np.int32),
                                     max_new_tokens=gen),
                     admitted_at=0.0, admit_step=0)


# ------------------------------------------------------------ exceptions

def test_pool_exhausted_is_typed_backpressure():
    """Both pools refuse capacity with one typed exception the engine can
    catch — a RuntimeError subclass, so untyped callers still fail loud.
    Page-budget refusals carry machine-readable ``pages_needed`` /
    ``pages_free`` (schedulers decide from numbers, not message parsing);
    non-page refusals leave both ``None``. Every refusal names *which*
    request hit the wall (``uid``) and *where* (``reason``: admission vs
    decode-time growth) — ``Engine.run()`` stats surface the events under
    ``"backpressure"`` keyed by that reason."""
    assert issubclass(PoolExhausted, RuntimeError)
    cfg = CASES[0]
    m = bind(cfg)
    single = _fake_single(m, 4)

    contiguous = SlotPool(m, capacity=1, max_seq=8)
    contiguous.admit(_entry("a"), single)
    with pytest.raises(PoolExhausted, match="full") as exc:
        contiguous.admit(_entry("b"), single)
    assert exc.value.pages_needed is None and exc.value.pages_free is None
    assert exc.value.uid == "b" and exc.value.reason == "admission"

    paged = PagedSlotPool(m, capacity=2, max_seq=16, block=4, n_blocks=2)
    paged.admit(_entry("c", prompt_len=4, gen=2), single)      # 1 page
    with pytest.raises(PoolExhausted, match="pages") as exc:
        paged.admit(_entry("d", prompt_len=8, gen=2),
                    _fake_single(m, 8))                        # needs 2
    assert exc.value.pages_needed == 3     # ceil((8 prompt + 2 gen) / 4)
    assert exc.value.pages_free == 1
    assert exc.value.uid == "d" and exc.value.reason == "admission"
    # decode-time growth hits the same typed refusal when the pool is dry,
    # attributed to the *growing* request and reason="decode"
    paged.admit(_entry("e", prompt_len=4, gen=2), single)
    with pytest.raises(PoolExhausted) as exc:
        paged.ensure_page(0, 4)                                # page 1 of 'c'
    assert exc.value.pages_needed == 1 and exc.value.pages_free == 0
    assert exc.value.uid == "c" and exc.value.reason == "decode"
    # ...and over-length growth is refused even with pages free
    roomy = PagedSlotPool(m, capacity=1, max_seq=8, block=4)
    roomy.admit(_entry("f", prompt_len=4, gen=2), single)
    with pytest.raises(PoolExhausted, match="max_seq") as exc:
        roomy.ensure_page(0, 8)
    assert exc.value.pages_needed is None and exc.value.pages_free is None
    assert exc.value.uid == "f" and exc.value.reason == "decode"


# ------------------------------------------------------------ round-trip

def test_paged_insert_read_roundtrip_all_families():
    """insert -> read through the block table recovers the single-sequence
    cache (up to the pool's longer, zero-padded sequence axis) for every
    family — the paged analogue of the contiguous slot contract."""
    for cfg in CASES:
        m = bind(cfg)
        params = _params(cfg)
        tokens = jnp.asarray(_prompt(cfg, 8, seed=1))[None]
        _, single = m.prefill_step(params, {"tokens": tokens})
        pool = PagedSlotPool(m, capacity=3, max_seq=12, block=4)
        slot = pool.admit(_entry("a", prompt_len=8, gen=3), single)
        back = pool.read(slot)
        flat_s, _ = jax.tree_util.tree_flatten(single)
        flat_b, _ = jax.tree_util.tree_flatten(back)
        for s, b in zip(flat_s, flat_b):
            if s.ndim == 1:                      # pos vector
                np.testing.assert_array_equal(np.asarray(s), np.asarray(b))
                continue
            sl = tuple(slice(0, e) for e in s.shape)
            np.testing.assert_array_equal(
                np.asarray(s), np.asarray(b[sl]), err_msg=cfg.name)
            # the tail beyond the inserted extents stays zero
            assert float(jnp.abs(b).sum()) == pytest.approx(
                float(jnp.abs(b[sl]).sum())), cfg.name


def test_evicted_pages_come_back_zeroed():
    cfg = CASES[0]
    m = bind(cfg)
    pool = PagedSlotPool(m, capacity=2, max_seq=12, block=4, n_blocks=4)
    slot = pool.admit(_entry("a", prompt_len=8, gen=1), _fake_single(m, 8))
    owned = pool.tables[slot][pool.tables[slot] >= 0].tolist()
    assert len(owned) == 2 and pool.pages_in_use == 2
    pool.evict(slot)
    assert pool.pages_in_use == 0
    assert (pool.tables == -1).all()
    for leaf in jax.tree.leaves(pool.cache):
        assert float(jnp.abs(leaf).sum()) == 0.0


def test_page_edits_compile_per_bucket_not_per_length(dense_params):
    """Serving prompts of 16 lengths compiles one admission program per
    prompt bucket and one eviction (its page list padded to the table's
    width): the page edits never compile per prompt length or count of
    pages, and the streams are what the contiguous pool serves."""
    from repro.launch.steps import bucket_for
    from repro.models import cache_ops
    cfg = CASES[0]
    lengths = list(range(9, 41, 2))
    reqs = [Request(uid=f"r{n}", prompt=_prompt(cfg, n, n), max_new_tokens=3)
            for n in lengths]
    eng = Engine(cfg, dense_params, capacity=2, max_seq=48, block=8,
                 chunk=8, prefix_cache=False)
    n_insert = cache_ops._insert._cache_size()
    n_evict = cache_ops._evict._cache_size()
    out = eng.run(reqs)
    buckets = {bucket_for(n, eng.buckets) for n in lengths}
    assert cache_ops._insert._cache_size() - n_insert <= len(buckets)
    assert cache_ops._evict._cache_size() - n_evict <= 1
    ref = Engine(cfg, dense_params, capacity=2, max_seq=48, paged=False,
                 chunk=8).run([Request(uid=r.uid, prompt=r.prompt,
                                       max_new_tokens=3) for r in reqs])
    for a, b in zip(out, ref):
        np.testing.assert_array_equal(a.tokens, b.tokens)


# ------------------------------------------- bookkeeping invariant fuzz

def _check_invariants(pool: PagedSlotPool):
    free = set(pool._free_pages)
    owned = [p for row in pool.tables for p in row[row >= 0].tolist()]
    assert len(owned) == len(set(owned)), "page double-owned"
    assert not (free & set(owned)), "page both free and owned"
    warm = {p for p in pool.retained if pool.refcount[p] == 0}
    assert not (free & warm), "warm retained page left on the free list"
    assert free | set(owned) | warm == set(range(pool.n_blocks)), \
        "page leaked (trash block must never be handed out)"
    assert pool.pages_in_use == len(owned) + len(warm)
    # the refcount ledger mirrors the block tables exactly (no pins here)
    refs = np.zeros(pool.n_blocks, np.int64)
    if owned:
        np.add.at(refs, owned, 1)
    assert (pool.refcount == refs).all(), "refcount ledger desync"
    live_rows = set(pool.entries)
    for slot in range(pool.capacity):
        row = pool.tables[slot]
        if slot not in live_rows:
            assert (row == -1).all(), "free slot kept pages"
        else:
            assert (row >= 0).any(), "live slot owns no pages"


def _assert_drained(pool: PagedSlotPool):
    """Post-drain refcount invariants (DESIGN.md §12): no live references,
    no negative refcounts, and every page is either free or a warm
    (refcount-0) page the prefix tree retains — i.e. nothing leaked."""
    assert pool.pages_live == 0
    assert (pool.refcount >= 0).all()
    assert pool.free_pages + len(pool.retained) == pool.n_blocks
    for p in pool.retained:
        assert pool.refcount[p] == 0, "retained page still referenced"


def _assert_refcount_ledger(engine):
    """Mid-run ledger check: the pool's refcounts equal block-table
    references plus the staging prefill's pinned prefix pages — nothing
    else may hold a reference, and none may go negative."""
    pool = engine.pool
    refs = np.zeros(pool.n_blocks, np.int64)
    for row in pool.tables:
        pages = row[row >= 0]
        if pages.size:
            np.add.at(refs, pages, 1)
    staging = engine._staging
    if staging is not None and staging.match is not None:
        np.add.at(refs, np.asarray(staging.match.pages, int), 1)
    assert (pool.refcount == refs).all(), "refcount ledger desync"
    assert (pool.refcount >= 0).all()


@settings(max_examples=30, deadline=None)
@given(st.data())
def test_page_bookkeeping_fuzz(data):
    """Randomized admit/grow/evict schedules never break the free-list /
    block-table invariants, regardless of interleaving or exhaustion."""
    cfg = CASES[0]
    m = bind(cfg)
    capacity = data.draw(st.integers(2, 3), "capacity")
    block = data.draw(st.sampled_from([2, 4]), "block")
    max_seq = 16
    n_blocks = data.draw(st.integers(2, capacity * (max_seq // block)),
                         "n_blocks")
    pool = PagedSlotPool(m, capacity, max_seq, block=block, n_blocks=n_blocks)
    uid = 0
    for _ in range(12):
        op = data.draw(st.sampled_from(["admit", "grow", "evict"]), "op")
        if op == "admit":
            plen = data.draw(st.integers(1, 8), "plen")
            entry = _entry(f"u{uid}", prompt_len=plen, gen=4)
            uid += 1
            try:
                pool.admit(entry, _fake_single(m, plen))
            except PoolExhausted:
                pass                      # refusal must keep state intact
        elif op == "grow" and pool.entries:
            slot = data.draw(st.sampled_from(sorted(pool.entries)), "slot")
            plen = pool.entries[slot].request.prompt_len
            try:
                pool.ensure_page(slot, data.draw(
                    st.integers(plen, max_seq - 1), "wpos"))
            except PoolExhausted:
                pass
        elif op == "evict" and pool.entries:
            slot = data.draw(st.sampled_from(sorted(pool.entries)), "slot")
            pool.evict(slot)
        _check_invariants(pool)


# --------------------------------------------------- engine backpressure

def test_engine_requeues_on_decode_time_exhaustion(dense_params):
    """A page budget too tight for both requests' full lengths forces a
    decode-time PoolExhausted; the engine must preempt + re-queue (never
    die) and still return bit-identical streams for *both* requests."""
    cfg = CASES[0]
    params = dense_params
    prompts = [_prompt(cfg, 4, seed=2), _prompt(cfg, 4, seed=3)]
    gens = [8, 6]
    baseline = [np.asarray(generate(cfg, params, jnp.asarray(p)[None],
                                    gen_tokens=g))[0]
                for p, g in zip(prompts, gens)]
    # each request peaks at 6/5 pages of 2; 8 total forces preemption
    engine = Engine(cfg, params, capacity=2, max_seq=12, block=2, n_blocks=8)
    results = engine.run([Request(uid=f"r{i}", prompt=p, max_new_tokens=g)
                          for i, (p, g) in enumerate(zip(prompts, gens))])
    assert engine.stats["preemptions"] >= 1
    for res, ref in zip(results, baseline):
        np.testing.assert_array_equal(res.tokens, ref, err_msg=res.uid)
    assert not engine.queue and not engine.pool.entries
    _assert_drained(engine.pool)
    # the exhaustion that forced preemption is attributed in run() stats:
    # decode-time events name the growing request and the shortfall
    decode_events = engine.stats["backpressure"]["decode"]
    assert decode_events, "decode-time exhaustion left no backpressure event"
    for ev in decode_events:
        assert set(ev) == {"uid", "pages_needed", "pages_free"}
        assert ev["uid"] in {"r0", "r1"}


def test_paged_pool_admits_what_contiguous_cannot(dense_params):
    """The acceptance shape of the benchmark's long-tail workload: under one
    shared token budget, the contiguous pool (stripe = budget / capacity)
    refuses the long request outright while the paged pool drains the whole
    workload by giving the long sequence many pages and the short ones
    few."""
    cfg = CASES[0]
    params = dense_params
    budget_tokens = 48                           # = 2 slots x 24-token stripe
    reqs = [Request(uid="long", prompt=_prompt(cfg, 4, 5), max_new_tokens=28),
            Request(uid="s0", prompt=_prompt(cfg, 4, 6), max_new_tokens=4),
            Request(uid="s1", prompt=_prompt(cfg, 4, 7), max_new_tokens=4)]

    contiguous = Engine(cfg, params, capacity=2, max_seq=budget_tokens // 2,
                        paged=False)
    with pytest.raises(PoolExhausted):
        contiguous.run(reqs)

    paged = Engine(cfg, params, capacity=2, max_seq=32, block=4,
                   n_blocks=budget_tokens // 4)
    results = paged.run(reqs)
    assert [r.n_generated for r in results] == [28, 4, 4]
    assert paged.stats["peak_pages"] <= budget_tokens // 4
    baseline = np.asarray(generate(cfg, params,
                                   jnp.asarray(reqs[0].prompt)[None],
                                   gen_tokens=28))[0]
    np.testing.assert_array_equal(results[0].tokens, baseline)


# ------------------------------------------------- bit-identity property

def _stream_schedule_case(data, families):
    cfg = data.draw(st.sampled_from(families), "family")
    block = data.draw(st.sampled_from([2, 4]), "block")
    capacity = data.draw(st.integers(1, 2), "capacity")
    n_req = data.draw(st.integers(2, 4), "n_req")
    # prompt lengths drawn from a small set so the prefill executable count
    # (one per length) stays bounded across examples; multiples of the SSM
    # scan chunk so every family accepts them
    plens = [data.draw(st.sampled_from([4, 8]), "plen") for _ in range(n_req)]
    gens = [data.draw(st.integers(1, 4), "gen") for _ in range(n_req)]
    max_seq = 16
    full = capacity * (max_seq // block)
    tight = max(-(-max(p + g for p, g in zip(plens, gens)) // block), 2)
    n_blocks = tight if data.draw(st.sampled_from([0, 1]), "tight") else full
    return cfg, block, capacity, plens, gens, max_seq, n_blocks


def _assert_paged_matches_sequential(data, families):
    cfg, block, capacity, plens, gens, max_seq, n_blocks = \
        _stream_schedule_case(data, families)
    params = _params(cfg)
    prompts = [_prompt(cfg, s, seed=10 + i) for i, (s, g)
               in enumerate(zip(plens, gens))]
    baseline = [np.asarray(generate(cfg, params, jnp.asarray(p)[None],
                                    gen_tokens=g))[0]
                for p, g in zip(prompts, gens)]
    engine = Engine(cfg, params, capacity=capacity, max_seq=max_seq,
                    block=block, n_blocks=n_blocks)
    results = engine.run([Request(uid=f"r{i}", prompt=p, max_new_tokens=g)
                          for i, (p, g) in enumerate(zip(prompts, gens))])
    for res, ref in zip(results, baseline):
        np.testing.assert_array_equal(
            res.tokens, ref,
            err_msg=(f"{cfg.name}: capacity={capacity} block={block} "
                     f"n_blocks={n_blocks} plens={plens} gens={gens}"))
    _assert_drained(engine.pool)


@settings(max_examples=4, deadline=None)
@given(st.data())
def test_paged_streams_bit_identical_fuzz(data):
    """Randomized schedules through the paged engine reproduce the
    sequential baseline bit-for-bit, drawing across all three families
    (the slow sweep runs many more examples)."""
    _assert_paged_matches_sequential(data, CASES)


@pytest.mark.slow
@settings(max_examples=24, deadline=None)
@given(st.data())
def test_paged_streams_bit_identical_fuzz_deep(data):
    """The long sweep (scheduled CI / `pytest -m slow`): all three families,
    more schedules, tight and roomy page budgets."""
    _assert_paged_matches_sequential(data, CASES)


# ----------------------------------------------- prefix cache (DESIGN §12)

def test_prefix_tree_match_insert_reclaim():
    """Pure radix-tree bookkeeping: match plans, registration, protocol
    violations, and LRU reclaim order — no model, no pool."""
    tree = PrefixCache(block=4, align=1)
    prompt = np.arange(8, dtype=np.int32)
    assert not tree.match(prompt).hit                # cold tree misses
    assert tree.insert(prompt, [5, 9]) == [5, 9]
    assert tree.owns(5) and tree.owns(9) and len(tree) == 2
    m = tree.match(prompt)
    # resume caps at prompt_len - 1 = 7: the final token's chunk is always
    # recomputed, so page 9 (holding position 7) is the CoW source
    assert m.hit and m.resume == 7 and m.pages == (5, 9)
    assert m.shared == (5,) and m.cow_src == 9
    # a longer prompt extending the resident prefix resumes page-aligned:
    # both pages attach by reference, nothing is copied
    longer = np.concatenate([prompt, np.arange(100, 104, dtype=np.int32)])
    m2 = tree.match(longer)
    assert m2.resume == 8 and m2.shared == (5, 9) and m2.cow_src is None
    # divergence in the first block is a clean miss, not a partial hit
    other = prompt.copy()
    other[0] ^= 1
    assert not tree.match(other).hit
    # registering one physical page under two prefixes is a violation...
    with pytest.raises(PrefixCacheInvariantError, match="two prefixes"):
        tree.insert(np.arange(50, 54, dtype=np.int32), [5])
    # ...as is a page list that does not tile the prompt
    with pytest.raises(PrefixCacheInvariantError, match="got 3 pages"):
        tree.insert(prompt, [1, 2, 3])
    # re-inserting resident content retains nothing new (the duplicate
    # pages stay private to their slot)
    assert tree.insert(prompt, [7, 8]) == []
    # reclaim surrenders idle leaves only — never an interior node while
    # its extension is resident — and frees the parent once the leaf goes
    refcount = np.zeros(16, np.int64)
    assert tree.reclaim(1, refcount) == [9]
    assert tree.reclaim(4, refcount) == [5]
    assert len(tree) == 0


def test_prefix_match_resume_is_chunk_aligned():
    """The chunked-prefill step scatters whole chunks at the staging
    offset, so resume offsets must round *down* to a chunk multiple; when
    that lands mid-page the page becomes the CoW source."""
    tree = PrefixCache(block=8, align=4)
    prompt = np.arange(16, dtype=np.int32)
    tree.insert(prompt, [0, 1])
    m = tree.match(prompt)
    # cap = 15 rounds down to 12 — inside page 1, which must be copied
    assert m.resume == 12 and m.pages == (0, 1)
    assert m.shared == (0,) and m.cow_src == 1
    aligned = PrefixCache(block=4, align=4)
    aligned.insert(prompt[:4], [3])
    m2 = aligned.match(prompt[:5])
    assert m2.resume == 4 and m2.shared == (3,) and m2.cow_src is None
    # an exactly-one-block prompt still recomputes its final token's
    # chunk, which rounds resume to zero: a miss, never a stale logit
    assert not aligned.match(prompt[:4]).hit


def test_prefix_hash_seed_only_permutes_keys():
    """The hash seed keys the radix digests, nothing else: match plans are
    identical across seeds because matching verifies raw tokens."""
    prompt = np.arange(12, dtype=np.int32)
    trees = [PrefixCache(block=4, seed=s, align=4) for s in (0, 7, -3)]
    for tree in trees:
        tree.insert(prompt, [0, 1, 2])
    plans = [tree.match(prompt) for tree in trees]
    assert plans[0] == plans[1] == plans[2]


def test_prefix_cache_gating(dense_params):
    """The cache engages only where sharing is sound: paged + chunked +
    dense (ssm/hybrid recurrent state is slot-scoped and cannot be
    recovered from K/V pages)."""
    cfg = CASES[0]
    on = Engine(cfg, dense_params, capacity=2, max_seq=16, block=4, chunk=4)
    assert on.prefix is not None and on.pool.prefix is on.prefix
    off = Engine(cfg, dense_params, capacity=2, max_seq=16, block=4,
                 chunk=4, prefix_cache=False)
    assert off.prefix is None
    oneshot = Engine(cfg, dense_params, capacity=2, max_seq=16, block=4,
                     prefill_mode="oneshot")
    assert oneshot.prefix is None
    contiguous = Engine(cfg, dense_params, capacity=2, max_seq=16,
                        paged=False)
    assert contiguous.prefix is None
    ssm_cfg = CASES[1]
    ssm = Engine(ssm_cfg, _params(ssm_cfg), capacity=2, max_seq=16,
                 block=4, chunk=4)
    assert ssm.prefix is None


def test_prefix_cache_shared_prompts_bit_identical(dense_params):
    """Shared prompts through the warm engine: streams stay bit-identical
    to the sequential baseline while prefill work is skipped, and a second
    run over the warm tree hits on every request."""
    cfg = CASES[0]
    params = dense_params
    base = _prompt(cfg, 16, seed=21)
    prompts = [base, base.copy(),
               np.concatenate([base[:8], _prompt(cfg, 8, seed=22)])]
    gens = [4, 3, 4]
    baseline = [np.asarray(generate(cfg, params, jnp.asarray(p)[None],
                                    gen_tokens=g))[0]
                for p, g in zip(prompts, gens)]

    engine = Engine(cfg, params, capacity=2, max_seq=32, block=4, chunk=4)
    results = engine.run([Request(uid=f"p{i}", prompt=p, max_new_tokens=g)
                          for i, (p, g) in enumerate(zip(prompts, gens))])
    for res, ref in zip(results, baseline):
        np.testing.assert_array_equal(res.tokens, ref, err_msg=res.uid)
    st = engine.stats
    assert st["prefix_cache"] and st["prefix_hits"] >= 1
    assert st["prefill_tokens_saved"] > 0 and st["prefix_hit_rate"] > 0
    _assert_drained(engine.pool)
    # the drained pool keeps the prefix warm: pages in use but none live
    assert engine.pool.pages_in_use > 0 and len(engine.pool.retained) > 0

    rerun = engine.run([Request(uid=f"q{i}", prompt=p, max_new_tokens=g)
                        for i, (p, g) in enumerate(zip(prompts, gens))])
    for res, ref in zip(rerun, baseline):
        np.testing.assert_array_equal(res.tokens, ref, err_msg=res.uid)
    st2 = engine.stats
    assert st2["prefix_hits"] == len(prompts) and st2["prefix_misses"] == 0
    assert st2["prefill_tokens_saved"] >= st["prefill_tokens_saved"]
    _assert_drained(engine.pool)


def test_prefix_cow_preserves_bit_identity(dense_params):
    """block > chunk forces the chunk-aligned resume mid-page, so
    admission must copy-on-write the straddled page; the suffix prefill
    then overwrites only rows above the resume point."""
    cfg = CASES[0]
    params = dense_params
    prompt = _prompt(cfg, 16, seed=31)
    gens = [3, 5]
    baseline = [np.asarray(generate(cfg, params, jnp.asarray(prompt)[None],
                                    gen_tokens=g))[0] for g in gens]
    engine = Engine(cfg, params, capacity=2, max_seq=32, block=8, chunk=4)
    results = engine.run([Request(uid=f"c{i}", prompt=prompt,
                                  max_new_tokens=g)
                          for i, g in enumerate(gens)])
    assert engine.stats["cow_copies"] >= 1
    assert engine.stats["prefix_hits"] >= 1
    for res, ref in zip(results, baseline):
        np.testing.assert_array_equal(res.tokens, ref, err_msg=res.uid)
    _assert_drained(engine.pool)


def test_prefix_match_ticks_only_used_pages():
    """match() refreshes recency for the pages the plan *uses* only:
    matched pages beyond the rounded-down resume keep their age, so an
    unused deep page never out-competes genuinely warm pages for
    retention."""
    tree = PrefixCache(block=4, align=4)
    a = np.arange(12, dtype=np.int32)
    b = np.arange(100, 108, dtype=np.int32)
    tree.insert(a, [0, 1, 2])
    tree.insert(b, [3, 4])
    m = tree.match(a)              # cap 11 -> resume 8: page 2 goes unused
    assert m.resume == 8 and m.pages == (0, 1)
    # page 2 kept its insert-time tick, so it is the LRU victim — branch
    # b's leaf (page 4), touched later, must survive it
    assert tree.reclaim(1, np.zeros(8, np.int64)) == [2]


def test_prefix_cow_admits_on_minimal_budget(dense_params):
    """An exactly-minimal page budget (n_blocks == pages_for(prompt+1),
    accepted by check_fits) must admit a CoW prefix hit: the pinned CoW
    source frees at admission, so can_admit credits it instead of holding
    the request forever and dying at the empty-pool check."""
    cfg = CASES[0]
    params = dense_params
    prompt = _prompt(cfg, 16, seed=51)
    baseline = np.asarray(generate(cfg, params, jnp.asarray(prompt)[None],
                                   gen_tokens=2))[0]
    engine = Engine(cfg, params, capacity=2, max_seq=24, block=8, chunk=4,
                    n_blocks=3)
    results = engine.run([Request(uid=f"m{i}", prompt=prompt.copy(),
                                  max_new_tokens=2) for i in range(2)])
    for res in results:
        np.testing.assert_array_equal(res.tokens, baseline, err_msg=res.uid)
    assert engine.stats["prefix_hits"] == 1
    assert engine.stats["cow_copies"] == 1         # sharing survived
    _assert_drained(engine.pool)


def test_prefix_hit_falls_back_to_private_admission(dense_params):
    """When even the credited plan cannot fit (chunk ∤ block leaves the
    resume mid-page with no shared pages, so the hit pins capacity private
    admission needs), the engine drops the sharing plan at the empty-pool
    check and admits the completed staging cache like a miss — never a
    PoolExhausted crash for a request that serves with the cache off."""
    cfg = CASES[0]
    params = dense_params
    prompt = _prompt(cfg, 15, seed=52)
    baseline = np.asarray(generate(cfg, params, jnp.asarray(prompt)[None],
                                   gen_tokens=1))[0]
    engine = Engine(cfg, params, capacity=2, max_seq=16, block=8, chunk=3,
                    n_blocks=2)
    results = engine.run([Request(uid=f"f{i}", prompt=prompt.copy(),
                                  max_new_tokens=1) for i in range(2)])
    for res in results:
        np.testing.assert_array_equal(res.tokens, baseline, err_msg=res.uid)
    # the hit happened (its skipped span still counts as saved — those
    # tokens were seeded, never recomputed), but admission went private
    assert engine.stats["prefix_hits"] == 1
    assert engine.stats["cow_copies"] == 0
    assert engine.stats["prefill_tokens_saved"] == 6
    _assert_drained(engine.pool)


def test_prefill_tokens_saved_counts_at_admission(dense_params):
    """The saved-token stat accrues when a hit *admits*, not when it
    stages: a preempted staging prefill re-stages (and re-matches), so a
    staging-time count would tally the same request twice."""
    cfg = CASES[0]
    params = dense_params
    prompt = _prompt(cfg, 16, seed=53)
    engine = Engine(cfg, params, capacity=2, max_seq=32, block=4, chunk=4)
    engine.run([Request(uid="warm", prompt=prompt, max_new_tokens=2)])
    def saved():
        return engine.counters()["prefill_tokens_saved"]

    saved0 = saved()
    engine.submit(Request(uid="x", prompt=prompt.copy(), max_new_tokens=2))
    engine._staging = engine._start_prefill(engine.queue.pop())
    assert engine._staging.match is not None       # warm tree: a hit
    assert saved() == saved0                       # nothing yet
    engine._preempt_youngest()                     # drop staging, requeue
    assert saved() == saved0                       # still nothing
    results = engine.run([])                       # re-stage + admit
    assert [r.uid for r in results] == ["x"]
    # counted exactly once, at admission: resume = 12 for this geometry
    assert saved() == saved0 + 12
    _assert_drained(engine.pool)


def test_prefix_hash_seed_stream_invariance(dense_params):
    """Engine streams and hit counts are invariant to the radix hash seed
    (serve.py --prefix-block-hash): the seed permutes tree keys only."""
    cfg = CASES[0]
    base = _prompt(cfg, 16, seed=41)
    prompts = [base, base.copy()]
    outs, hits = [], []
    for seed in (0, 123456789):
        engine = Engine(cfg, dense_params, capacity=2, max_seq=32, block=4,
                        chunk=4, prefix_hash_seed=seed)
        results = engine.run([
            Request(uid=f"h{i}", prompt=p, max_new_tokens=4)
            for i, p in enumerate(prompts)])
        outs.append([r.tokens for r in results])
        hits.append(engine.stats["prefix_hits"])
    for a, b in zip(*outs):
        np.testing.assert_array_equal(a, b)
    assert hits[0] == hits[1] >= 1


# --------------------------------------- shared-prefix schedule property

def _shared_prefix_case(data, families):
    """A schedule built to exercise sharing: many requests over few long
    common prompts, divergent suffixes, and (optionally) a page budget
    tight enough to force preemption + LRU reclaim of warm pages."""
    cfg = data.draw(st.sampled_from(families), "family")
    block = data.draw(st.sampled_from([2, 4]), "block")
    capacity = data.draw(st.integers(1, 2), "capacity")
    n_req = data.draw(st.integers(3, 4), "n_req")
    max_seq = 32
    rng = np.random.default_rng(data.draw(st.integers(0, 3), "base_seed"))
    base = rng.integers(0, cfg.vocab_size, size=(12,)).astype(np.int32)
    prompts, gens = [], []
    for i in range(n_req):
        shape = data.draw(st.sampled_from(["full", "full", "short", "div"]),
                          f"shape{i}")
        if shape == "full":          # the whole common prompt, verbatim
            prompt = base.copy()
        elif shape == "short":       # a block-aligned ancestor prefix
            prompt = base[:8].copy()
        else:                        # shared head, divergent tail
            tail = rng.integers(0, cfg.vocab_size, size=(4,))
            prompt = np.concatenate([base[:8], tail]).astype(np.int32)
        prompts.append(prompt)
        gens.append(data.draw(st.integers(1, 4), f"gen{i}"))
    full = capacity * (max_seq // block)
    tight = max(-(-max(len(p) + g for p, g in zip(prompts, gens)) // block),
                2)
    n_blocks = tight if data.draw(st.sampled_from([0, 1]), "tight") else full
    return cfg, block, capacity, prompts, gens, max_seq, n_blocks


def _assert_shared_prefix_schedule(data, families):
    cfg, block, capacity, prompts, gens, max_seq, n_blocks = \
        _shared_prefix_case(data, families)
    params = _params(cfg)
    baseline = [np.asarray(generate(cfg, params, jnp.asarray(p)[None],
                                    gen_tokens=g))[0]
                for p, g in zip(prompts, gens)]
    engine = Engine(cfg, params, capacity=capacity, max_seq=max_seq,
                    block=block, n_blocks=n_blocks, chunk=4)
    for i, (p, g) in enumerate(zip(prompts, gens)):
        engine.submit(Request(uid=f"r{i}", prompt=p, max_new_tokens=g))
    while engine.step():
        _assert_refcount_ledger(engine)      # no page freed at refcount>0
    results = engine.run([])                 # collect + populate stats
    by_uid = {r.uid: r for r in results}
    for i, ref in enumerate(baseline):
        np.testing.assert_array_equal(
            by_uid[f"r{i}"].tokens, ref,
            err_msg=(f"{cfg.name}: capacity={capacity} block={block} "
                     f"n_blocks={n_blocks} "
                     f"plens={[len(p) for p in prompts]} gens={gens}"))
    _assert_drained(engine.pool)             # no leak at drain


@settings(max_examples=3, deadline=None)
@given(st.data())
def test_shared_prefix_streams_bit_identical_fuzz(data):
    """Shared-prefix schedules (the workload the cache exists for) stay
    bit-identical to the sequential baseline across all three families —
    dense shares pages, ssm/hybrid must be transparently unaffected —
    with refcount bookkeeping checked at every engine step."""
    _assert_shared_prefix_schedule(data, CASES)


@pytest.mark.slow
@settings(max_examples=20, deadline=None)
@given(st.data())
def test_shared_prefix_streams_bit_identical_fuzz_deep(data):
    """The deep shared-prefix sweep (scheduled CI / `pytest -m slow`):
    more schedules, including tight budgets that force preemption churn
    and LRU reclaim of the warm prefix set."""
    _assert_shared_prefix_schedule(data, CASES)
