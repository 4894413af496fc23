"""Fused paged-attention kernel acceptance (ISSUE 5 / DESIGN.md §9).

Three layers, cheapest first:

* *kernel bit-identity*, parametrized: ``paged_attention_pallas`` against
  the gathered-dense reference (``paged_gather``-equivalent gather +
  ``decode_attention``) across block sizes, page budgets, fragmented /
  shuffled block tables, GQA ratios, sliding windows, dtypes, and every
  valid KV-heads-per-step — ``np.testing.assert_array_equal``, no
  tolerance. Full-MHA (g = 1) layouts ride the whole-row finish path
  (ISSUE 6) and get the same zero-tolerance treatment;
* *dispatch*: the eligibility gate routes softcap and single-KV-head
  layouts to the gathered-dense fallback, serves full-MHA through the
  kernel, and ``kernel_impl`` resolves like the flash kernel's;
* *the headline invariant*, through the real engine: fused streams (both
  the "auto" per-layer-gather path this CPU resolves to and the forced
  Pallas kernel) are **bit-identical** to the sequential per-request
  ``generate()`` baseline for dense, SSM, and hybrid families with SC-GEMM
  on — including fragmented tables from eviction churn and tight budgets
  that force preemption. The deep sweep runs under ``pytest -m slow``
  (the scheduled CI job).

Fuzzing goes through ``tests/_propcheck.py``: hypothesis when installed,
deterministic fixed-seed sweeps otherwise.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _propcheck import given, settings, st
from repro.configs.base import ModelConfig
from repro.kernels.autotune import (PagedFlashConfig, candidate_paged_configs,
                                    get_or_tune_paged)
from repro.kernels.paged_attention import paged_attention_pallas
from repro.launch.serve import generate
from repro.models import bind
from repro.models.layers import (PagedKV, _paged_kernel_eligible,
                                 decode_attention, paged_decode_attention)
from repro.serving import Engine, Request


# --------------------------------------------------------------- fixtures

def _problem(seed, *, c, h, kv, d, mb, block, extra_pages=2,
             dtype=jnp.float32):
    """A fragmented paged-attention problem: random pages assigned to slots
    in shuffled (non-contiguous) order, random unallocated tails, positions
    inside each slot's last allocated page. Returns the kernel operands."""
    rng = np.random.default_rng(seed)
    n_pages = c * mb + extra_pages            # last page = trash block
    kp = jnp.asarray(rng.standard_normal((n_pages, block, kv, d)), dtype)
    vp = jnp.asarray(rng.standard_normal((n_pages, block, kv, d)), dtype)
    q = jnp.asarray(rng.standard_normal((c, 1, h, d)), dtype)
    perm = rng.permutation(n_pages - 1)       # never hand out the trash page
    tables = np.full((c, mb), -1, np.int32)
    pos = np.zeros(c, np.int32)
    k = 0
    for i in range(c):
        n = int(rng.integers(1, mb + 1))
        tables[i, :n] = perm[k:k + n]
        k += n
        pos[i] = rng.integers((n - 1) * block, n * block)
    return q, kp, vp, jnp.asarray(tables), jnp.asarray(pos)


@functools.partial(jax.jit, static_argnames=("window", "logit_softcap"))
def _dense_reference(q, kp, vp, tables, pos, window=None, logit_softcap=None):
    """The gathered-dense path the kernel must reproduce bitwise: the same
    trash-redirected gather ``cache_ops.paged_gather`` performs, then the
    stock ``decode_attention`` — jitted, because the engine's baseline
    decode step is jitted too."""
    c, mb = tables.shape
    block = kp.shape[1]
    safe = jnp.where(tables < 0, kp.shape[0] - 1, tables)
    kc = kp[safe].reshape(c, mb * block, *kp.shape[2:])
    vc = vp[safe].reshape(c, mb * block, *vp.shape[2:])
    return decode_attention(q, kc, vc, q_position=pos, window=window,
                            logit_softcap=logit_softcap)


def _kernel_out(q, kp, vp, tables, pos, *, kvh, window=None,
                logit_softcap=None):
    c, _, h, d = q.shape
    kv = kp.shape[2]
    g = h // kv
    out = paged_attention_pallas(q[:, 0].reshape(c, kv, g, d), kp, vp,
                                 tables, pos, window=window,
                                 logit_softcap=logit_softcap, kvh=kvh,
                                 interpret=True)
    return out.reshape(c, 1, h, d)


# --------------------------------------------------- kernel bit-identity

GEOMETRIES = [
    # (c, h, kv, d, mb, block, window)
    (3, 4, 2, 16, 4, 4, None),      # fragmented multi-page tables
    (2, 8, 4, 16, 3, 2, None),      # tiny pages, wider GQA
    (1, 4, 1, 16, 8, 2, None),      # single slot, deep table
    (3, 4, 2, 16, 4, 4, 6),         # sliding window straddling pages
    (2, 4, 2, 32, 2, 8, 5),         # window + wider head dim
    (4, 8, 2, 16, 1, 16, None),     # single-page table (MB = 1)
    (2, 6, 2, 16, 3, 4, None),      # odd group size g = 3
    (3, 4, 4, 16, 4, 4, None),      # full-MHA (g = 1, whole-row finish)
    (2, 4, 4, 16, 3, 4, 6),         # full-MHA + sliding window
    (2, 8, 8, 32, 2, 4, None),      # full-MHA, wide heads, kvh up to 8
]


@pytest.mark.parametrize("c,h,kv,d,mb,block,window", GEOMETRIES)
def test_kernel_bit_identical_to_gathered_dense(c, h, kv, d, mb, block,
                                                window):
    """Every geometry, every valid kvh: exact equality with the jitted
    gathered-dense reference — the DESIGN.md §9 contract the engine's
    stream identity rests on."""
    q, kp, vp, tables, pos = _problem(c * 131 + mb, c=c, h=h, kv=kv, d=d,
                                      mb=mb, block=block)
    ref = _dense_reference(q, kp, vp, tables, pos, window=window)
    for cfg in candidate_paged_configs(kv, h // kv, d, block=block,
                                       max_blocks=mb):
        out = _kernel_out(q, kp, vp, tables, pos, kvh=cfg.kvh, window=window)
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(ref),
            err_msg=f"kvh={cfg.kvh} geometry={(c, h, kv, d, mb, block)} "
                    f"window={window}")


def test_kernel_bit_identical_bf16():
    q, kp, vp, tables, pos = _problem(7, c=3, h=4, kv=2, d=16, mb=4, block=4,
                                      dtype=jnp.bfloat16)
    ref = _dense_reference(q, kp, vp, tables, pos)
    out = _kernel_out(q, kp, vp, tables, pos, kvh=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_kernel_bit_identical_bf16_full_mha():
    """g == 1 buffers *raw* K pages (cache dtype, no cast) so the whole-row
    score einsum sees exactly the operands the gathered-dense path sees —
    the bf16 case is where a sneaky fp32 upcast would show."""
    q, kp, vp, tables, pos = _problem(11, c=3, h=4, kv=4, d=16, mb=4, block=4,
                                      dtype=jnp.bfloat16)
    ref = _dense_reference(q, kp, vp, tables, pos)
    out = _kernel_out(q, kp, vp, tables, pos, kvh=2)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_kernel_tight_budget_reuses_pages_exactly():
    """A budget barely above one slot's need: page ids collide across time
    (eviction churn shape) — the kernel must read exactly what the table
    says, not assume contiguous allocation."""
    rng = np.random.default_rng(11)
    c, h, kv, d, mb, block = 2, 4, 2, 16, 4, 4
    n_pages = 5                                # 4 live + trash
    kp = jnp.asarray(rng.standard_normal((n_pages, block, kv, d)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((n_pages, block, kv, d)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((c, 1, h, d)), jnp.float32)
    # reversed/interleaved assignment of the 4 real pages
    tables = jnp.asarray(np.array([[3, 1, -1, -1], [0, 2, -1, -1]], np.int32))
    pos = jnp.asarray(np.array([6, 7], np.int32))
    ref = _dense_reference(q, kp, vp, tables, pos)
    for kvh in (1, 2):
        out = _kernel_out(q, kp, vp, tables, pos, kvh=kvh)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_kernel_free_slot_reads_trash_without_corrupting_live_rows():
    """A free slot (all table entries -1, drifted pos) redirects every page
    read to the trash block; the live rows must still be exact."""
    q, kp, vp, tables, pos = _problem(13, c=3, h=4, kv=2, d=16, mb=3, block=4)
    tables = tables.at[1].set(-1)              # slot 1 freed
    pos = pos.at[1].set(5)                     # drifted free-slot position
    ref = _dense_reference(q, kp, vp, tables, pos)
    out = _kernel_out(q, kp, vp, tables, pos, kvh=1)
    live = np.array([0, 2])
    np.testing.assert_array_equal(np.asarray(out)[live],
                                  np.asarray(ref)[live])


def test_kernel_softcap_close_but_gated():
    """Softcap is supported by the kernel (allclose) but sits outside the
    bit-identity envelope — the tanh chain fuses differently per program —
    so the dispatch gate must refuse it."""
    q, kp, vp, tables, pos = _problem(17, c=3, h=4, kv=2, d=16, mb=4, block=4)
    ref = _dense_reference(q, kp, vp, tables, pos, logit_softcap=30.0)
    out = _kernel_out(q, kp, vp, tables, pos, kvh=1, logit_softcap=30.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5,
                               rtol=1e-5)
    assert not _paged_kernel_eligible(2, 16, 4, 30.0, True)
    # full-MHA is in the envelope via the whole-row finish einsum — but
    # only when a kvh >= 2 grid split exists, so kv == 1 stays gathered
    assert _paged_kernel_eligible(1, 16, 4, None, True)
    assert not _paged_kernel_eligible(1, 16, 4, None, True, kv=1)
    assert _paged_kernel_eligible(2, 16, 4, None, True)
    # a whole-row scratch past the VMEM budget has no tuning candidate —
    # the gate must route it to the gather instead of letting the tuner
    # raise "no tuning candidates" inside a jitted decode step
    assert _paged_kernel_eligible(4, 128, 16, None, True, kv=8,
                                  max_blocks=4)
    assert not _paged_kernel_eligible(4, 128, 16, None, True, kv=8,
                                      max_blocks=2048)
    # compiled (non-interpret) backends never take the kernel: Mosaic
    # refuses it at every layout, so even an aligned GQA layout gathers
    assert not _paged_kernel_eligible(4, 128, 16, None, False, kv=8,
                                      max_blocks=4)


def test_kernel_rejects_non_dividing_kvh():
    q, kp, vp, tables, pos = _problem(37, c=2, h=8, kv=4, d=16, mb=2, block=4)
    with pytest.raises(ValueError, match="must divide"):
        paged_attention_pallas(q[:, 0].reshape(2, 4, 2, 16), kp, vp, tables,
                               pos, kvh=3, interpret=True)


def test_kernel_rejects_full_mha_single_head_step():
    """g == 1 with kvh == 1 is outside the bit-identity envelope (a
    single-head whole-row slice lowers to a different contraction) — the
    kernel refuses it rather than return close-but-off bits."""
    q, kp, vp, tables, pos = _problem(41, c=2, h=4, kv=4, d=16, mb=2, block=4)
    with pytest.raises(ValueError, match="kvh >= 2"):
        paged_attention_pallas(q[:, 0].reshape(2, 4, 1, 16), kp, vp, tables,
                               pos, kvh=1, interpret=True)


# ------------------------------------------------------- layer dispatch

def test_layer_dispatch_kernel_matches_jnp_bitwise():
    """models.layers.paged_decode_attention: "pallas_tuned" (forced kernel)
    and "jnp" (gathered-dense) agree bitwise on eligible layouts, and the
    autotune cache serves a PagedFlashConfig for the swept key."""
    q, kp, vp, tables, pos = _problem(19, c=2, h=4, kv=2, d=16, mb=3, block=4)
    paged = PagedKV(kp, vp, tables)
    out_jnp = paged_decode_attention(q, paged, q_position=pos,
                                     kernel_impl="jnp")
    out_kernel = paged_decode_attention(q, paged, q_position=pos,
                                        kernel_impl="pallas_tuned")
    np.testing.assert_array_equal(np.asarray(out_kernel), np.asarray(out_jnp))
    with pytest.raises(ValueError, match="kernel_impl"):
        paged_decode_attention(q, paged, q_position=pos, kernel_impl="mosaic")


def test_layer_dispatch_full_mha_uses_kernel_bitwise():
    """Full-MHA (g == 1, kv >= 2) is served by the kernel's whole-row
    finish path — forced dispatch must be bitwise the gathered-dense
    result, same contract as the GQA layouts."""
    q, kp, vp, tables, pos = _problem(31, c=2, h=2, kv=2, d=16, mb=3, block=4)
    paged = PagedKV(kp, vp, tables)
    out = paged_decode_attention(q, paged, q_position=pos,
                                 kernel_impl="pallas_tuned")
    ref = _dense_reference(q, kp, vp, tables, pos)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_layer_dispatch_ineligible_falls_back():
    """Single-KV-head full-MHA (h == kv == 1: no kvh >= 2 grid split
    exists, so the tuning grid is empty) forced to "pallas_tuned" must
    still serve the gathered-dense result — the eligibility gate, not the
    caller, owns the envelope."""
    q, kp, vp, tables, pos = _problem(23, c=2, h=1, kv=1, d=16, mb=3, block=4)
    paged = PagedKV(kp, vp, tables)
    out = paged_decode_attention(q, paged, q_position=pos,
                                 kernel_impl="pallas_tuned")
    ref = _dense_reference(q, kp, vp, tables, pos)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_get_or_tune_paged_caches_per_geometry(tmp_path):
    from repro.kernels.autotune import AutotuneCache
    cache = AutotuneCache(tmp_path / "tune.json")
    q, kp, vp, tables, pos = _problem(29, c=2, h=4, kv=2, d=16, mb=2, block=4)
    cfg = get_or_tune_paged(q[:, 0].reshape(2, 2, 2, 16), kp, vp, tables,
                            pos, cache=cache, iters=1, interpret=True)
    assert isinstance(cfg, PagedFlashConfig) and cfg.is_valid()
    again = get_or_tune_paged(q[:, 0].reshape(2, 2, 2, 16), kp, vp, tables,
                              pos, cache=cache, iters=1, interpret=True)
    assert again == cfg                        # served from the cache
    assert len(cache) == 1


# --------------------------------------------- engine stream bit-identity

def _cfg(family, **kw):
    base = dict(name=f"pa-{family}", family=family, n_layers=2, d_model=64,
                n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
                vocab_size=128, dtype="float32", q_block=16, kv_block=16,
                loss_chunk=16, remat=False, use_sc_gemm=True)
    base.update(kw)
    return ModelConfig(**base).validate()


#: GQA head layouts (g = 2) so the forced-kernel runs exercise the per-page
#: score path on every attention site; the full-MHA (g = 1, whole-row
#: finish) kernel path gets its own engine run in
#: test_fused_engine_full_mha_streams_bit_identical, and the remaining
#: gather fallback (kv == 1) in test_layer_dispatch_ineligible_falls_back.
FAMILIES = [
    _cfg("dense"),
    _cfg("ssm", n_kv_heads=1, d_ff=0, ssm_state=16, ssm_headdim=16,
         ssm_chunk=4),
    _cfg("hybrid", n_kv_heads=2, ssm_state=16, ssm_headdim=16, ssm_chunk=4,
         shared_attn_every=2, n_layers=4),
]


def _force_kernel(cfg):
    return dataclasses.replace(cfg, paged_attn_kernel="pallas_tuned").validate()


def _streams_match_baseline(cfg, *, capacity, block, n_blocks, plens, gens,
                            max_seq=16, fused=True, seed=100):
    params = bind(cfg).init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, size=(s,)).astype(np.int32)
               for s in plens]
    baseline = [np.asarray(generate(cfg, params, jnp.asarray(p)[None],
                                    gen_tokens=g))[0]
                for p, g in zip(prompts, gens)]
    engine = Engine(cfg, params, capacity=capacity, max_seq=max_seq,
                    block=block, n_blocks=n_blocks, fused=fused)
    results = engine.run([Request(uid=f"r{i}", prompt=p, max_new_tokens=g)
                          for i, (p, g) in enumerate(zip(prompts, gens))])
    for res, ref in zip(results, baseline):
        np.testing.assert_array_equal(
            res.tokens, ref,
            err_msg=(f"{cfg.name} paged_attn={cfg.paged_attn_kernel} "
                     f"fused={fused} capacity={capacity} block={block} "
                     f"n_blocks={n_blocks}"))
    # drained: no live references (prefix-warm pages may remain resident)
    assert engine.pool.pages_live == 0
    assert (engine.pool.free_pages + len(engine.pool.retained)
            == engine.pool.n_blocks)
    return engine


@pytest.mark.parametrize("cfg", FAMILIES, ids=lambda c: c.name)
def test_fused_engine_streams_bit_identical(cfg):
    """The acceptance headline: fused paged decode — forced through the
    Pallas kernel on every eligible attention site — reproduces the
    sequential baseline bit-for-bit for all three families."""
    _streams_match_baseline(_force_kernel(cfg), capacity=2, block=4,
                            n_blocks=None, plens=[4, 4, 8], gens=[6, 3, 5])


def test_fused_engine_full_mha_streams_bit_identical():
    """Full-MHA (H == KV) end-to-end: the whole-row kernel path — not the
    gather fallback this layout used to take — forced on every attention
    site, streams still bit-identical to the sequential baseline."""
    cfg = _force_kernel(_cfg("dense", n_kv_heads=4))
    _streams_match_baseline(cfg, capacity=2, block=4, n_blocks=None,
                            plens=[4, 8], gens=[5, 4])


def test_fused_engine_survives_preemption_churn():
    """Tight budget → decode-time preemption → fragmented tables on
    re-admission; the fused kernel must still be exact through the churn."""
    cfg = _force_kernel(FAMILIES[0])
    engine = _streams_match_baseline(cfg, capacity=2, block=2, n_blocks=8,
                                     max_seq=12, plens=[4, 4],
                                     gens=[8, 6], seed=2)
    assert engine.stats["preemptions"] >= 1


def test_fused_matches_gather_engine_logits_path():
    """fused=True vs fused=False builders drain the same workload to the
    same streams — the two decode structures are interchangeable."""
    cfg = FAMILIES[0]
    params = bind(cfg).init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(31)
    reqs = lambda: [Request(uid=f"r{i}",
                            prompt=rng.integers(0, cfg.vocab_size,
                                                size=(4,)).astype(np.int32),
                            max_new_tokens=g)
                    for i, g in enumerate([5, 3, 6])]
    rng = np.random.default_rng(31)
    a = Engine(cfg, params, capacity=2, max_seq=16, block=4).run(reqs())
    rng = np.random.default_rng(31)
    b = Engine(cfg, params, capacity=2, max_seq=16, block=4,
               fused=False).run(reqs())
    for ra, rb in zip(a, b):
        np.testing.assert_array_equal(ra.tokens, rb.tokens, err_msg=ra.uid)


# ------------------------------------------------------------ deep sweep

def _fuzz_case(data):
    cfg = data.draw(st.sampled_from(FAMILIES), "family")
    impl = data.draw(st.sampled_from(["auto", "pallas_tuned"]), "impl")
    cfg = dataclasses.replace(cfg, paged_attn_kernel=impl).validate()
    block = data.draw(st.sampled_from([2, 4]), "block")
    capacity = data.draw(st.integers(1, 2), "capacity")
    n_req = data.draw(st.integers(2, 4), "n_req")
    plens = [data.draw(st.sampled_from([4, 8]), "plen") for _ in range(n_req)]
    gens = [data.draw(st.integers(1, 4), "gen") for _ in range(n_req)]
    max_seq = 16
    full = capacity * (max_seq // block)
    tight = max(-(-max(p + g for p, g in zip(plens, gens)) // block), 2)
    n_blocks = tight if data.draw(st.sampled_from([0, 1]), "tight") else full
    return cfg, capacity, block, n_blocks, plens, gens


@settings(max_examples=4, deadline=None)
@given(st.data())
def test_fused_streams_bit_identical_fuzz(data):
    """Randomized schedules through the fused engine (kernel forced or
    auto-dispatched) reproduce the sequential baseline bit-for-bit."""
    cfg, capacity, block, n_blocks, plens, gens = _fuzz_case(data)
    _streams_match_baseline(cfg, capacity=capacity, block=block,
                            n_blocks=n_blocks, plens=plens, gens=gens)


@pytest.mark.slow
@settings(max_examples=24, deadline=None)
@given(st.data())
def test_fused_streams_bit_identical_fuzz_deep(data):
    """The long sweep (scheduled CI / `pytest -m slow`): all three
    families, both dispatch modes, tight and roomy budgets."""
    cfg, capacity, block, n_blocks, plens, gens = _fuzz_case(data)
    _streams_match_baseline(cfg, capacity=capacity, block=block,
                            n_blocks=n_blocks, plens=plens, gens=gens)
