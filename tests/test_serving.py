"""Serving engine acceptance (ISSUE 3 / DESIGN.md §7, ISSUE 6 / §10).

* slot-pool invariants: admit/evict bookkeeping, slot reuse, overflow
  refusal, insert/read round-trip through the uniform cache contract;
* the headline invariant: with SC-GEMM enabled, continuous-batching token
  streams are **bit-identical** to the sequential per-request
  ``launch.serve.generate`` baseline for all three model families;
* scheduling: a mixed-length 8-request workload finishes in strictly fewer
  batched decode steps under continuous batching than static batching;
* eviction-on-EOS: streams truncate exactly where the sequential stream
  first emits the EOS id;
* streaming surface: per-request callbacks fire in stream order with the
  first token strictly before completion (TTFT < latency), the pull
  generator dedupes preemption replays, and a preempted stream re-emits
  its prefix bit-identically;
* chunked prefill: for every family, chunked admission == one-shot
  admission == the sequential baseline token-for-token (property-fuzzed
  over prompt lengths, chunk sizes, and capacities; the deep sweep runs
  under ``pytest -m slow``), and prompt bucketing bounds the number of
  compiled prefill executables by the bucket set, not the number of
  distinct prompt lengths.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _propcheck import given, settings, st
from repro.configs.base import ModelConfig
from repro.launch.serve import generate
from repro.models import bind
from repro.models.cache_ops import slot_insert
from repro.serving import (Engine, PoolExhausted, Request, RequestQueue,
                           SlotEntry, SlotPool)


def _cfg(family, **kw):
    base = dict(name=f"srv-{family}", family=family, n_layers=2, d_model=64,
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


def _params(cfg):
    return bind(cfg).init_params(jax.random.PRNGKey(0))


def _prompts(cfg, n, s=8, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, size=(s,)).astype(np.int32)
            for _ in range(n)]


# ------------------------------------------------------------- slot pool

def test_slot_pool_admit_evict_reuse():
    cfg = CASES[0]
    m = bind(cfg)
    pool = SlotPool(m, capacity=2, max_seq=12)
    params = _params(cfg)
    prefill = lambda p: m.prefill_step(params, {"tokens": jnp.asarray(p)[None]})

    def entry(uid, gen=2):
        return SlotEntry(request=Request(uid=uid, prompt=_prompts(cfg, 1)[0],
                                         max_new_tokens=gen),
                         admitted_at=0.0, admit_step=0)

    _, c0 = prefill(_prompts(cfg, 1)[0])
    s0 = pool.admit(entry("a"), c0)
    s1 = pool.admit(entry("b"), c0)
    assert {s0, s1} == {0, 1} and not pool.has_free and len(pool) == 2
    with pytest.raises(PoolExhausted, match="full"):
        pool.admit(entry("c"), c0)

    # eviction zeroes the slot and hands back the lowest index first
    pool.evict(s0)
    assert pool.has_free and pool.positions()[s0] == 0
    assert pool.admit(entry("d"), c0) == s0          # reuse after eviction
    # over-length requests are refused before touching device state —
    # typed (PoolExhausted) so the engine can route it as backpressure
    pool.evict(s0)
    with pytest.raises(PoolExhausted, match="max_seq"):
        pool.admit(entry("e", gen=100), c0)
    assert pool.has_free                             # refusal kept the slot


def test_slot_insert_read_roundtrip_all_families():
    """insert -> read recovers the single-sequence cache (up to the pool's
    longer, zero-padded sequence axis) for every family: the uniform
    contract the engine rests on."""
    for cfg in CASES:
        m = bind(cfg)
        params = _params(cfg)
        tokens = jnp.asarray(_prompts(cfg, 1)[0])[None]
        _, single = m.prefill_step(params, {"tokens": tokens})
        pool = m.init_cache(3, 12)
        pool = slot_insert(pool, single, 1)
        back = m.cache_read(pool, 1)
        flat_s, _ = jax.tree_util.tree_flatten(single)
        flat_b, _ = jax.tree_util.tree_flatten(back)
        for s, b in zip(flat_s, flat_b):
            if s.ndim == 1:                      # pos vector
                np.testing.assert_array_equal(np.asarray(s), np.asarray(b))
                continue
            sl = tuple(slice(0, e) for e in s.shape)
            np.testing.assert_array_equal(np.asarray(s), np.asarray(b[sl]))
            # the tail beyond the inserted extents stays zero
            assert float(jnp.abs(b).sum()) == pytest.approx(
                float(jnp.abs(b[sl]).sum()))


def test_engine_rejects_oversized_request_before_any_work():
    """An unfittable request fails at run() entry — before prefill, before
    queueing — so it can never abort a run mid-flight and discard finished
    streams; the engine stays usable afterwards."""
    cfg = CASES[0]
    engine = Engine(cfg, _params(cfg), capacity=1, max_seq=10)
    good = Request(uid="fits", prompt=_prompts(cfg, 1)[0], max_new_tokens=2)
    bad = Request(uid="big", prompt=_prompts(cfg, 1)[0], max_new_tokens=99)
    with pytest.raises(PoolExhausted, match="max_seq"):
        engine.run([good, bad])
    assert not engine.queue and not engine.pool.entries
    assert engine.run([good])[0].n_generated == 2


def test_request_queue_fcfs_and_duplicate_uid():
    q = RequestQueue([Request(uid="a", prompt=np.ones(4, np.int32),
                              max_new_tokens=1)])
    q.submit(Request(uid="b", prompt=np.ones(4, np.int32), max_new_tokens=1))
    with pytest.raises(ValueError, match="duplicate"):
        q.submit(Request(uid="a", prompt=np.ones(4, np.int32),
                         max_new_tokens=1))
    assert q.pop().uid == "a" and q.pop().uid == "b" and not q


# ------------------------------------------------- bit-identical decoding

@pytest.mark.parametrize("cfg", CASES, ids=lambda c: c.name)
def test_engine_streams_bit_identical_to_sequential(cfg):
    """Continuous batching (capacity 2, SC-GEMM on, paged cache with
    4-token pages) reproduces the sequential per-request baseline exactly —
    token-for-token — while co-batching requests admitted at different
    times. tests/test_paging.py fuzzes the same invariant over randomized
    schedules and page budgets."""
    params = _params(cfg)
    prompts = _prompts(cfg, 5)
    gens = [3, 7, 2, 5, 4]
    baseline = [np.asarray(generate(cfg, params, jnp.asarray(p)[None],
                                    gen_tokens=g))[0]
                for p, g in zip(prompts, gens)]

    engine = Engine(cfg, params, capacity=2, max_seq=8 + max(gens), block=4)
    results = engine.run([Request(uid=f"r{i}", prompt=p, max_new_tokens=g)
                          for i, (p, g) in enumerate(zip(prompts, gens))])
    for res, ref in zip(results, baseline):
        np.testing.assert_array_equal(res.tokens, ref,
                                      err_msg=f"{cfg.name}/{res.uid}")
        assert res.finished_reason == "length"
    # slots really were shared: fewer decode steps than sequential's total
    assert engine.stats["decode_steps"] < sum(g - 1 for g in gens)


def test_engine_eos_eviction_matches_truncated_baseline():
    """EOS eviction: pick the baseline's 3rd token as the EOS id — the
    engine must emit the identical prefix and stop there, freeing the slot
    for the next request."""
    cfg = CASES[0]
    params = _params(cfg)
    prompts = _prompts(cfg, 2, seed=3)
    full = [np.asarray(generate(cfg, params, jnp.asarray(p)[None],
                                gen_tokens=8))[0] for p in prompts]
    eos = int(full[0][2])

    engine = Engine(cfg, params, capacity=1, max_seq=16)
    results = engine.run([
        Request(uid="eos", prompt=prompts[0], max_new_tokens=8, eos_id=eos),
        Request(uid="tail", prompt=prompts[1], max_new_tokens=8),
    ])
    cut = int(np.argmax(full[0] == eos)) + 1
    np.testing.assert_array_equal(results[0].tokens, full[0][:cut])
    assert results[0].finished_reason == "eos"
    np.testing.assert_array_equal(results[1].tokens, full[1])


# --------------------------------------------------------- scheduling A/B

def test_mixed_workload_fewer_steps_than_static():
    """Acceptance: an 8-request mixed-length workload drains in strictly
    fewer batched decode steps under continuous batching than static
    batching, with identical streams from both modes — and across cache
    layouts (the continuous engine runs paged, the static one contiguous,
    so layout can never leak into the tokens)."""
    cfg = dataclasses.replace(CASES[0], use_sc_gemm=False)
    params = _params(cfg)
    prompts = _prompts(cfg, 8, seed=5)
    gens = [2, 12, 3, 12, 2, 12, 3, 12]

    def reqs():
        return [Request(uid=f"r{i}", prompt=p, max_new_tokens=g)
                for i, (p, g) in enumerate(zip(prompts, gens))]

    cont = Engine(cfg, params, capacity=4, max_seq=24, continuous=True,
                  paged=True, block=8)
    r_cont = cont.run(reqs())
    stat = Engine(cfg, params, capacity=4, max_seq=24, continuous=False,
                  paged=False)
    r_stat = stat.run(reqs())

    assert cont.stats["decode_steps"] < stat.stats["decode_steps"], (
        cont.stats, stat.stats)
    for a, b in zip(r_cont, r_stat):
        np.testing.assert_array_equal(a.tokens, b.tokens)
    assert cont.stats["generated_tokens"] == sum(gens)


# ------------------------------------------------------ streaming surface

def test_streaming_callbacks_in_order_and_ttft_precedes_completion():
    """on_token callbacks deliver each request's stream in order (indexes
    0, 1, 2, ... as decode steps land), matching the collected
    RequestResult token-for-token, with the finish reason only on the last
    event — and the first token's wall-clock strictly precedes completion,
    so TTFT is a real streaming latency, not latency renamed."""
    cfg = CASES[0]
    params = _params(cfg)
    prompts = _prompts(cfg, 3, seed=7)
    gens = [5, 3, 6]
    events: dict[str, list] = {}

    def on_token(uid, index, tok, reason):
        events.setdefault(uid, []).append((index, np.asarray(tok), reason))

    engine = Engine(cfg, params, capacity=2, max_seq=16, block=4)
    for i, (p, g) in enumerate(zip(prompts, gens)):
        engine.submit(Request(uid=f"r{i}", prompt=p, max_new_tokens=g),
                      on_token=on_token)
    results = {r.uid: r for r in engine.run()}

    assert set(events) == set(results) == {f"r{i}" for i in range(3)}
    for uid, evs in events.items():
        res = results[uid]
        assert [e[0] for e in evs] == list(range(res.n_generated))
        np.testing.assert_array_equal(np.stack([e[1] for e in evs]),
                                      res.tokens, err_msg=uid)
        assert [e[2] for e in evs] == [None] * (len(evs) - 1) + ["length"]
        assert res.first_token_at < res.finished_at
        assert 0 < res.ttft_s <= res.latency_s


def test_stream_generator_yields_sequential_baseline():
    """The pull-driven generator yields the request's tokens one by one —
    bit-identical to the sequential baseline — while a co-batched request
    keeps decoding and finishes in the same drain."""
    cfg = CASES[0]
    params = _params(cfg)
    prompts = _prompts(cfg, 2, seed=9)
    baseline = np.asarray(generate(cfg, params, jnp.asarray(prompts[0])[None],
                                   gen_tokens=6))[0]
    engine = Engine(cfg, params, capacity=2, max_seq=16, block=4)
    engine.submit(Request(uid="other", prompt=prompts[1], max_new_tokens=3))
    toks = list(engine.stream(Request(uid="s", prompt=prompts[0],
                                      max_new_tokens=6)))
    np.testing.assert_array_equal(np.stack(toks), baseline)
    leftover = engine.run()           # drain the co-batched request
    assert {r.uid for r in leftover} == {"other"}
    assert not engine.pool.entries


def test_preempted_stream_replays_bit_identically():
    """Decode-time page exhaustion preempts a stream mid-flight; its
    callback re-emits the stream from index 0 on re-admission. Replayed
    indexes must carry the *same* tokens (determinism), TTFT keeps the
    first emission (not the re-admission), and first-occurrence dedupe
    reconstructs the exact sequential stream."""
    cfg = CASES[0]
    params = _params(cfg)
    prompts = _prompts(cfg, 2, seed=2)[:1] + _prompts(cfg, 2, seed=3)[:1]
    prompts = [p[:4] for p in prompts]
    gens = [8, 6]
    baseline = [np.asarray(generate(cfg, params, jnp.asarray(p)[None],
                                    gen_tokens=g))[0]
                for p, g in zip(prompts, gens)]
    events: dict[str, list] = {}

    def on_token(uid, index, tok, reason):
        events.setdefault(uid, []).append((index, np.asarray(tok)))

    # each request peaks at 6/5 pages of 2; 8 total forces preemption
    engine = Engine(cfg, params, capacity=2, max_seq=12, block=2, n_blocks=8)
    for i, (p, g) in enumerate(zip(prompts, gens)):
        engine.submit(Request(uid=f"r{i}", prompt=p, max_new_tokens=g),
                      on_token=on_token)
    results = {r.uid: r for r in engine.run()}
    assert engine.stats["preemptions"] >= 1

    replayed = []
    for uid, evs in events.items():
        first_seen: dict[int, np.ndarray] = {}
        for index, tok in evs:
            if index in first_seen:
                replayed.append(uid)
                np.testing.assert_array_equal(tok, first_seen[index],
                                              err_msg=f"{uid}[{index}]")
            else:
                first_seen[index] = tok
        res = results[uid]
        assert sorted(first_seen) == list(range(res.n_generated))
        np.testing.assert_array_equal(
            np.stack([first_seen[i] for i in range(len(first_seen))]),
            res.tokens, err_msg=uid)
    assert replayed, "page budget never forced a replay"
    for uid in set(replayed):          # TTFT survives the preemption
        assert results[uid].first_token_at <= results[uid].admitted_at
    for res, ref in zip((results["r0"], results["r1"]), baseline):
        np.testing.assert_array_equal(res.tokens, ref, err_msg=res.uid)


# ------------------------------------------------------- chunked prefill

def test_prompt_bucketing_bounds_executables():
    """Six distinct prompt lengths, three buckets: the engine must reuse
    bucket executables instead of compiling one per length (the compiled
    count is what ``stats['prefill_executables']`` reports, and what the
    serving benchmark asserts on in CI)."""
    cfg = CASES[0]
    params = _params(cfg)
    rng = np.random.default_rng(13)
    plens = [2, 3, 5, 6, 7, 9]
    engine = Engine(cfg, params, capacity=2, max_seq=16, block=4, chunk=4)
    assert engine.buckets == (4, 8, 16)
    reqs = [Request(uid=f"r{i}",
                    prompt=rng.integers(0, cfg.vocab_size,
                                        size=(s,)).astype(np.int32),
                    max_new_tokens=2)
            for i, s in enumerate(plens)]
    engine.run(reqs)
    st_ = engine.stats
    assert st_["prefill_executables"] <= len(st_["buckets"]) < len(set(plens))


def _assert_chunked_matches_oneshot_and_sequential(data, families):
    """One drawn schedule: sequential baseline vs the engine under both
    prefill modes. Prompt lengths deliberately include non-multiples of
    the chunk (the final partial chunk is the case the ``n_valid`` masking
    must get exactly right — e.g. an aligned plen=4 against chunk=8)."""
    cfg = data.draw(st.sampled_from(families), "family")
    capacity = data.draw(st.integers(1, 2), "capacity")
    n_req = data.draw(st.integers(2, 3), "n_req")
    plens = [data.draw(st.sampled_from([3, 4, 7, 8, 12]), "plen")
             for _ in range(n_req)]
    if cfg.family != "dense":
        # the one-shot executable and the sequential baseline both require
        # ssm_chunk-aligned prompts (the SSD scan asserts l % chunk == 0);
        # only chunked prefill pads internally, so align the comparison
        # surface — partial final chunks still occur whenever plen < chunk
        plens = [-(-p // cfg.ssm_chunk) * cfg.ssm_chunk for p in plens]
    gens = [data.draw(st.integers(1, 4), "gen") for _ in range(n_req)]
    chunk = data.draw(st.sampled_from([4, 8]), "chunk")
    params = _params(cfg)
    rng = np.random.default_rng(1000 + sum(plens) + chunk)
    prompts = [rng.integers(0, cfg.vocab_size, size=(s,)).astype(np.int32)
               for s in plens]
    baseline = [np.asarray(generate(cfg, params, jnp.asarray(p)[None],
                                    gen_tokens=g))[0]
                for p, g in zip(prompts, gens)]
    tag = (f"{cfg.name}: capacity={capacity} chunk={chunk} "
           f"plens={plens} gens={gens}")
    for mode in ("chunked", "oneshot"):
        engine = Engine(cfg, params, capacity=capacity, max_seq=20, block=4,
                        prefill_mode=mode, chunk=chunk)
        results = engine.run([Request(uid=f"r{i}", prompt=p,
                                      max_new_tokens=g)
                              for i, (p, g) in enumerate(zip(prompts, gens))])
        for res, ref in zip(results, baseline):
            np.testing.assert_array_equal(res.tokens, ref,
                                          err_msg=f"{mode} {tag} {res.uid}")
        if mode == "chunked":
            st_ = engine.stats
            assert st_["prefill_executables"] <= len(st_["buckets"]), tag


@settings(max_examples=3, deadline=None)
@given(st.data())
def test_chunked_prefill_bit_identical_fuzz(data):
    """Chunked admission == one-shot admission == sequential baseline,
    drawn across all three families (the slow sweep runs many more)."""
    _assert_chunked_matches_oneshot_and_sequential(data, CASES)


@pytest.mark.slow
@settings(max_examples=16, deadline=None)
@given(st.data())
def test_chunked_prefill_bit_identical_fuzz_deep(data):
    """The long sweep (scheduled CI / `pytest -m slow`): more schedules,
    chunk sizes, and partial-final-chunk prompt lengths per family."""
    _assert_chunked_matches_oneshot_and_sequential(data, CASES)


# ------------------------------------------------ the device's greedy pick

class _HostSampling(Engine):
    """The engine sampling every row on the host: the row's logits fetched,
    checked for finite values and reduced by numpy (the reference the
    device's greedy pick must match token for token)."""

    def _sample(self, entry, row):
        row = np.asarray(row)
        if not np.isfinite(row).all():
            self._counts["nonfinite_logit_rows"] += 1
        req = entry.request
        if req.temperature <= 0:
            return np.argmax(row, axis=-1).astype(np.int32)
        if entry.key is None:
            entry.key = jax.random.PRNGKey(req.seed)
        entry.key, sub = jax.random.split(entry.key)
        tok = jax.random.categorical(
            sub, jnp.asarray(row) / req.temperature, axis=-1)
        return np.asarray(tok, np.int32)


#: (name, Engine keywords) of the paths that sample a decode or prompt row.
PATHS = [("chunked", {}), ("oneshot", {"prefill_mode": "oneshot"}),
         ("contiguous", {"paged": False}),
         ("static", {"continuous": False})]


def _doctor(logits):
    """Exact ties in every row (logits on a grid of quarters), two NaNs in
    row 0, two +inf in row 1 and a row of -inf in row 2."""
    x = jnp.round(logits * 4) / 4
    x = x.at[0, ..., 9].set(jnp.nan).at[0, ..., 3].set(jnp.nan)
    x = x.at[1, ..., 7].set(jnp.inf).at[1, ..., 2].set(jnp.inf)
    return x.at[2].set(-jnp.inf)


def _doctoring(real):
    def build(*a, **k):
        fn, shardings, abstract = real(*a, **k)

        def decode(*args):
            logits, cache = fn(*args)
            return _doctor(logits), cache
        return decode, shardings, abstract
    return build


def _mixed_requests(cfg, temperatures, seed=21):
    prompts = _prompts(cfg, len(temperatures), s=6, seed=seed)
    return [Request(uid=f"r{i}", prompt=p, max_new_tokens=3 + 2 * (i % 3),
                    temperature=t, seed=100 + i)
            for i, (p, t) in enumerate(zip(prompts, temperatures))]


def _run_both(cfg, params, kw, temperatures):
    out = []
    for cls in (Engine, _HostSampling):
        engine = cls(cfg, params, capacity=4, max_seq=16, block=4, chunk=4,
                     **kw)
        c0 = engine.counters()
        results = engine.run(_mixed_requests(cfg, temperatures))
        c1 = engine.counters()
        out.append((results, {k: c1[k] - c0[k] for k in c1}))
    return out


@pytest.mark.parametrize("kw", [kw for _, kw in PATHS],
                         ids=[n for n, _ in PATHS])
def test_device_pick_matches_host_argmax_on_ties_and_nonfinite_rows(
        kw, monkeypatch):
    """A decode step whose rows hold exact ties, NaN, +inf and -inf: the
    device's argmax takes the first maximum (the first NaN where there is
    one), as ``np.argmax`` does, and ``nonfinite_logit_rows`` counts the
    same rows as the host's ``np.isfinite``."""
    from repro.serving import engine as engine_mod
    for name in ("cached_paged_decode_step", "cached_decode_step"):
        monkeypatch.setattr(engine_mod, name,
                            _doctoring(getattr(engine_mod, name)))
    cfg = CASES[0]
    (got, c_dev), (want, c_host) = _run_both(cfg, _params(cfg), kw,
                                             [0.0] * 6)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g.tokens, w.tokens, err_msg=g.uid)
    assert c_dev["nonfinite_logit_rows"] == c_host["nonfinite_logit_rows"] > 0
    assert c_dev["logit_rows_fetched"] == 0


@pytest.mark.parametrize("kw", [kw for _, kw in PATHS],
                         ids=[n for n, _ in PATHS])
def test_mixed_greedy_and_sampled_batch_streams_unchanged(kw):
    """Greedy and ``temperature > 0`` requests share decode steps: every
    stream is the host sampler's, and only the sampled requests' rows cross
    to the host, padded to a power of two (here at most 3 rows to 4)."""
    cfg = CASES[0]
    temperatures = [0.0, 0.8, 0.0, 1.5, 0.0, 0.8]
    (got, c_dev), (want, _) = _run_both(cfg, _params(cfg), kw, temperatures)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g.tokens, w.tokens, err_msg=g.uid)
    sampled = sum(g.n_generated for g, t in zip(got, temperatures) if t > 0)
    greedy = sum(g.n_generated for g, t in zip(got, temperatures) if t <= 0)
    assert 0 < sampled <= c_dev["logit_rows_fetched"] <= 4 * sampled // 3
    assert c_dev["device_sampled_rows"] == greedy


@pytest.mark.parametrize("kw", [kw for _, kw in PATHS],
                         ids=[n for n, _ in PATHS])
def test_sampled_batch_makes_one_fetch_per_step(kw, monkeypatch):
    """A batch in which every request samples: each decode step and each
    admission makes one ``device_get``, which brings the sampled rows'
    logits with the tokens (at most 3 rows padded to 4), and no second
    fetch for any row."""
    from repro.serving import engine as engine_mod
    fetches = [0]
    real = jax.device_get

    def counting(x):
        fetches[0] += 1
        return real(x)
    monkeypatch.setattr(engine_mod.jax, "device_get", counting)
    cfg = CASES[0]
    engine = Engine(cfg, _params(cfg), capacity=4, max_seq=16, block=4,
                    chunk=4, **kw)
    c0 = engine.counters()
    results = engine.run(_mixed_requests(cfg, [0.8, 1.5, 0.8, 1.0, 0.8]))
    d = {k: v - c0[k] for k, v in engine.counters().items()}
    assert fetches[0] == d["decode_steps"] + d["prefills"]
    assert d["device_sampled_rows"] == 0
    sampled = sum(r.n_generated for r in results)
    assert 0 < sampled <= d["logit_rows_fetched"] <= 4 * sampled // 3


@pytest.mark.parametrize(
    "kw", [kw for _, kw in PATHS] + [{"n_blocks": 8},
                                     {"prefill_mode": "oneshot",
                                      "n_blocks": 8}],
    ids=[n for n, _ in PATHS] + ["preempting", "oneshot-preempting"])
def test_all_greedy_run_fetches_no_logit_row(kw):
    """An all-greedy run copies no logit row to the host: every decode row
    and every admission (a preempted request's again) takes the device's
    pick."""
    cfg = CASES[0]
    engine = Engine(cfg, _params(cfg), capacity=2, max_seq=12, block=2,
                    chunk=4, **kw)
    c0 = engine.counters()
    results = engine.run([Request(uid=f"r{i}", prompt=p, max_new_tokens=g)
                          for i, (p, g) in enumerate(zip(
                              _prompts(cfg, 3, s=4, seed=2), (8, 6, 5)))])
    d = {k: v - c0[k] for k, v in engine.counters().items()}
    assert d["logit_rows_fetched"] == 0
    assert d["device_sampled_rows"] == d["decode_rows"] + d["prefills"]
    assert d["device_sampled_rows"] >= sum(r.n_generated for r in results)
    assert (d["preemptions"] > 0) == ("n_blocks" in kw)
