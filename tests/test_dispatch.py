"""DESIGN.md §6 numeric dispatch: every ``sc_impl`` value is count-identical
through ``sc_dense``, resolution honors config -> $REPRO_SC_IMPL -> autotune
cache, the tuned paths are trace-safe, and model forwards resolve their block
configs through the interpret-flag-keyed cache."""
import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.configs.shapes import SHAPES, sc_gemm_problems
from repro.core import recover_counts, sc_dense
from repro.core.sc_matmul import IMPL_ENV, resolve_impl, sc_matmul
from repro.core.sc_layers import _sc_dense_fwd
from repro.models import bind

#: the config-facing dispatch space (ISSUE: "auto" | "mxu_split" | "pallas"
#: | "pallas_tuned" | "ref")
SC_IMPL_VALUES = ("ref", "mxu_split", "pallas", "pallas_tuned", "auto")


@pytest.fixture(scope="module", autouse=True)
def _shared_tune_cache(tmp_path_factory):
    """One throwaway autotune cache for the whole module: pallas_tuned sweeps
    each distinct problem shape once, later tests hit the cache."""
    path = tmp_path_factory.mktemp("autotune") / "tune.json"
    mp = pytest.MonkeyPatch()
    mp.setenv("REPRO_AUTOTUNE_CACHE", str(path))
    yield path
    mp.undo()


def _rand(key, shape):
    return jax.random.normal(key, shape, jnp.float32)


def _mini(shape):
    """Structure-preserving CPU-sized stand-in for a registered input shape:
    same kind (train/prefill/decode -> same set of GEMM call sites), extents
    capped so the count-identity sweep stays tractable in interpret mode."""
    return dataclasses.replace(shape, seq_len=min(shape.seq_len, 32),
                               global_batch=min(shape.global_batch, 2))


_DISPATCH_CFG = ModelConfig(
    name="dispatch-probe", family="dense", n_layers=2, d_model=48, n_heads=4,
    n_kv_heads=2, head_dim=12, d_ff=96, vocab_size=64, dtype="float32",
    loss_chunk=16).validate()


# ------------------------------------------------------- count identity

@pytest.mark.parametrize("impl", SC_IMPL_VALUES)
@pytest.mark.parametrize("shape_name", sorted(SHAPES))
def test_sc_dense_count_identity_across_impls(shape_name, impl):
    """Acceptance: every sc_impl config value produces identical de-scaled
    integer counts through the sc_dense forward, for the GEMM problems every
    registered input shape routes through it."""
    shape = _mini(SHAPES[shape_name])
    for m, k, n in sc_gemm_problems(_DISPATCH_CFG, shape):
        key = jax.random.PRNGKey(m * 31 + k * 7 + n)
        k1, k2 = jax.random.split(key)
        x, w = _rand(k1, (m, k)), _rand(k2, (k, n))
        ref_counts = recover_counts(sc_dense(x, w, 8, "ref"), x, w,
                                    row_quant=True)
        out = sc_dense(x, w, 8, impl)
        np.testing.assert_array_equal(
            recover_counts(out, x, w, row_quant=True), ref_counts,
            err_msg=f"impl={impl} diverged on ({m},{k})x({k},{n})")


def test_sc_matmul_ref_alias():
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    a, b = _rand(k1, (8, 16)), _rand(k2, (16, 8))
    np.testing.assert_array_equal(
        np.asarray(sc_matmul(a, b, impl="ref")),
        np.asarray(sc_matmul(a, b, impl="reference")))


# ------------------------------------------------------- impl resolution

def test_resolve_impl_order(monkeypatch):
    """config (explicit) -> $REPRO_SC_IMPL -> "auto" (backend/autotune)."""
    monkeypatch.delenv(IMPL_ENV, raising=False)
    assert resolve_impl(None) == "auto"
    assert resolve_impl("auto") == "auto"
    assert resolve_impl("pallas") == "pallas"
    monkeypatch.setenv(IMPL_ENV, "mxu_split")
    assert resolve_impl("auto") == "mxu_split"    # env fills the open choice
    assert resolve_impl("pallas") == "pallas"     # explicit config still wins
    monkeypatch.setenv(IMPL_ENV, "bogus")
    with pytest.raises(ValueError, match="REPRO_SC_IMPL"):
        resolve_impl("auto")


def test_resolve_impl_rejects_unknown():
    with pytest.raises(ValueError, match="unknown SC impl"):
        resolve_impl("systolic")
    with pytest.raises(ValueError, match="unknown SC impl"):
        sc_matmul(jnp.ones((4, 4)), jnp.ones((4, 4)), impl="systolic")


def test_env_override_reaches_sc_dense(monkeypatch):
    """$REPRO_SC_IMPL steers sc_dense's default dispatch end to end."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    x, w = _rand(k1, (8, 16)), _rand(k2, (16, 8))
    ref_counts = recover_counts(sc_dense(x, w, 8, "ref"), x, w,
                                row_quant=True)
    monkeypatch.setenv(IMPL_ENV, "pallas")
    np.testing.assert_array_equal(
        recover_counts(sc_dense(x, w, 8, None), x, w, row_quant=True),
        ref_counts)
    monkeypatch.setenv(IMPL_ENV, "bogus")
    with pytest.raises(ValueError, match="REPRO_SC_IMPL"):
        sc_dense(x, w, 8, None)


def test_model_config_validates_sc_impl():
    with pytest.raises(AssertionError, match="sc_impl"):
        dataclasses.replace(_DISPATCH_CFG, sc_impl="bogus").validate()
    with pytest.raises(AssertionError, match="attn_kernel"):
        dataclasses.replace(_DISPATCH_CFG, attn_kernel="bogus").validate()


# ------------------------------------------------------- dtype contract

def test_sc_dense_vjp_residuals_keep_caller_dtype():
    """bf16 training must not double activation memory: the VJP residuals are
    the caller's arrays in their original dtype (fp32 upcast happens only
    inside the kernel call and is never saved)."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(2))
    x = _rand(k1, (4, 16)).astype(jnp.bfloat16)
    w = _rand(k2, (16, 8)).astype(jnp.bfloat16)
    out, res = _sc_dense_fwd(x, w, 8, None)
    assert out.dtype == jnp.bfloat16
    assert res[0].dtype == jnp.bfloat16 and res[1].dtype == jnp.bfloat16

    def loss(x, w):
        return jnp.sum(sc_dense(x, w, 8, None).astype(jnp.float32))

    gx, gw = jax.grad(loss, argnums=(0, 1))(x, w)
    assert gx.dtype == jnp.bfloat16 and gw.dtype == jnp.bfloat16
    assert bool(jnp.all(jnp.isfinite(gx.astype(jnp.float32))))


# ------------------------------------------------------- trace safety

def test_tuned_matmul_inside_jit(tmp_path, monkeypatch):
    """tune=True under jax.jit must not leak tracers into the sweep: a miss
    resolves via a synthetic-data sweep at trace time and lands in the cache
    keyed with the interpret flag."""
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "tune.json"))
    from repro.kernels import ops
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    a, b = _rand(k1, (16, 32)), _rand(k2, (32, 16))

    jitted = jax.jit(lambda a, b: ops.sc_matmul_pallas(a, b, bits=8, tune=True))
    out = jitted(a, b)
    np.testing.assert_array_equal(
        recover_counts(out, a, b),
        recover_counts(sc_matmul(a, b, impl="ref"), a, b))
    doc = json.loads((tmp_path / "tune.json").read_text())
    keys = list(doc["entries"])
    assert keys and all(k.startswith("sc_gemm:") for k in keys)
    assert all(":interp:" in k for k in keys)   # CPU test runner


def test_autotune_rejects_raw_tracers():
    """The raw sweep entry point refuses traced operands with a clear error
    instead of a cryptic tracer leak."""
    from repro.kernels.autotune import autotune

    def traced(a, b):
        autotune(a, b, bits=8)
        return a

    with pytest.raises(TypeError, match="concrete"):
        jax.jit(traced)(jnp.ones((8, 16)), jnp.ones((16, 8)))


def test_transformer_forward_resolves_through_cache(tmp_path, monkeypatch):
    """Acceptance: a (jitted) transformer forward with sc_impl="pallas_tuned"
    resolves every projection's block config through the autotune cache —
    the cache file gains sc_gemm entries keyed with the interpret flag."""
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "tune.json"))
    cfg = dataclasses.replace(_DISPATCH_CFG, use_sc_gemm=True,
                              sc_impl="pallas_tuned").validate()
    m = bind(cfg)
    params = m.init_params(jax.random.PRNGKey(0))
    batch = {"tokens": jnp.ones((2, 16), jnp.int32)}
    hidden, _ = jax.jit(lambda p, b: m.forward_hidden(p, b))(params, batch)
    assert bool(jnp.all(jnp.isfinite(hidden)))
    doc = json.loads((tmp_path / "tune.json").read_text())
    keys = [k for k in doc["entries"] if k.startswith("sc_gemm:")]
    assert keys, "forward pass must populate the autotune cache"
    assert all(":interp:" in k for k in keys)

    # identical counts vs the reference numeric, end to end
    cfg_ref = dataclasses.replace(cfg, sc_impl="ref")
    h_ref, _ = bind(cfg_ref).forward_hidden(params, batch)
    np.testing.assert_allclose(np.asarray(hidden), np.asarray(h_ref),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------------------- model-level parity

@pytest.mark.parametrize("impl", ["mxu_split", "pallas"])
def test_model_families_sc_impl_parity(impl):
    """Dense/MoE/SSM/hybrid forwards agree exactly (same counts => allclose
    activations) between the reference numeric and each fast impl."""
    cases = {
        "dense": {},
        "moe": dict(d_ff=0, n_experts=4, top_k=2, moe_d_ff=32,
                    moe_flags=(True,), shared_expert_d_ff=16),
        "ssm": dict(n_heads=4, n_kv_heads=1, d_ff=0, ssm_state=16,
                    ssm_headdim=16, ssm_chunk=4),
        "hybrid": dict(ssm_state=16, ssm_headdim=16, ssm_chunk=4,
                       shared_attn_every=2, n_layers=4),
    }
    batch = {"tokens": jnp.ones((2, 16), jnp.int32)}
    for family, kw in cases.items():
        base = dict(name=f"par-{family}", family=family, n_layers=2,
                    d_model=48, n_heads=4, n_kv_heads=2, head_dim=12, d_ff=96,
                    vocab_size=64, dtype="float32", q_block=16, kv_block=16,
                    loss_chunk=16, remat=False, use_sc_gemm=True)
        base.update(kw)
        cfg = ModelConfig(**base, sc_impl=impl).validate()
        params = bind(cfg).init_params(jax.random.PRNGKey(0))
        h, _ = bind(cfg).forward_hidden(params, batch)
        cfg_ref = dataclasses.replace(cfg, sc_impl="ref")
        h_ref, _ = bind(cfg_ref).forward_hidden(params, batch)
        np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref),
                                   rtol=1e-4, atol=1e-4,
                                   err_msg=f"{family}/{impl}")


# ------------------------------------------------------- flash dispatch

def test_flash_attention_kernel_dispatch_matches_jnp():
    """layers.flash_attention(kernel_impl="pallas_tuned") routes eligible
    shapes through the tuned Pallas kernel (interpret mode here) and matches
    the jnp formulation; the (bq, bk) choice lands in the autotune cache."""
    from repro.models.layers import flash_attention
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    b, s, h, d = 1, 128, 2, 128
    q = jax.random.normal(kq, (b, s, h, d), jnp.float32)
    k = jax.random.normal(kk, (b, s, h, d), jnp.float32)
    v = jax.random.normal(kv, (b, s, h, d), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    kwargs = dict(q_positions=pos, kv_positions=pos, causal=True,
                  q_block=128, kv_block=128)
    out_jnp = flash_attention(q, k, v, kernel_impl="jnp", **kwargs)
    out_kernel = flash_attention(q, k, v, kernel_impl="pallas_tuned",
                                 canonical_positions=True, **kwargs)
    np.testing.assert_allclose(np.asarray(out_kernel), np.asarray(out_jnp),
                               rtol=2e-3, atol=2e-3)
    import os
    doc = json.loads(Path(os.environ["REPRO_AUTOTUNE_CACHE"]).read_text())
    assert any(key.startswith("flash:") for key in doc["entries"])
    # without the caller's canonical-positions declaration the kernel never
    # engages, even when forced and shape-eligible
    out_default = flash_attention(q, k, v, kernel_impl="pallas_tuned", **kwargs)
    np.testing.assert_array_equal(np.asarray(out_default), np.asarray(out_jnp))


def test_flash_kernel_dispatch_is_differentiable():
    """The Pallas flash kernel is forward-only; the dispatch wraps it in a
    recompute-based VJP through the jnp formulation, so training through
    kernel_impl="pallas_tuned" (and "auto" on TPU) must produce the jnp
    path's gradients instead of crashing in pallas_call's AD rule."""
    from repro.models.layers import flash_attention
    key = jax.random.PRNGKey(4)
    kq, kk, kv = jax.random.split(key, 3)
    b, s, h, d = 1, 128, 2, 128
    q = jax.random.normal(kq, (b, s, h, d), jnp.float32)
    k = jax.random.normal(kk, (b, s, h, d), jnp.float32)
    v = jax.random.normal(kv, (b, s, h, d), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))

    def loss(q, k, v, impl):
        out = flash_attention(q, k, v, q_positions=pos, kv_positions=pos,
                              causal=True, q_block=128, kv_block=128,
                              kernel_impl=impl, canonical_positions=True)
        return jnp.sum(out * out)

    gk = jax.grad(lambda *a: loss(*a, "pallas_tuned"), argnums=(0, 1, 2))(q, k, v)
    gj = jax.grad(lambda *a: loss(*a, "jnp"), argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gk, gj):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=5e-3, atol=5e-3)


def test_flash_kernel_respects_supplied_positions():
    """The fused kernel assumes canonical 0..S-1 positions; a forward with
    caller-supplied positions (packed/restarted sequences) must keep the
    position-aware jnp path even when attn_kernel requests the kernel."""
    from repro.models import transformer
    cfg = dataclasses.replace(
        _DISPATCH_CFG, n_heads=2, n_kv_heads=2, head_dim=128,   # kernel-eligible
        q_block=128, kv_block=128, remat=False,
        attn_kernel="pallas_tuned").validate()
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 128), 0,
                                cfg.vocab_size, dtype=jnp.int32)
    # two packed 64-token documents: positions restart mid-sequence
    packed = jnp.concatenate([jnp.arange(64), jnp.arange(64)])[None, :]
    batch = {"tokens": tokens, "positions_1d": packed.astype(jnp.int32)}
    h_kernel_cfg, _ = transformer.forward_hidden(params, cfg, batch)
    cfg_jnp = dataclasses.replace(cfg, attn_kernel="jnp")
    h_jnp, _ = transformer.forward_hidden(params, cfg_jnp, batch)
    np.testing.assert_array_equal(np.asarray(h_kernel_cfg), np.asarray(h_jnp))

    # canonical positions do dispatch to the kernel — and still agree
    canon = {"tokens": tokens}
    h_k, _ = transformer.forward_hidden(params, cfg, canon)
    h_j, _ = transformer.forward_hidden(params, cfg_jnp, canon)
    np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_j),
                               rtol=2e-3, atol=2e-3)


def test_flash_attention_kernel_ineligible_falls_back():
    """Windowed/softcapped/ragged calls silently use the jnp path."""
    from repro.models.layers import flash_attention
    key = jax.random.PRNGKey(1)
    kq, kk, kv = jax.random.split(key, 3)
    b, s, h, d = 1, 48, 2, 16       # ragged extents: never kernel-eligible
    q = jax.random.normal(kq, (b, s, h, d), jnp.float32)
    k = jax.random.normal(kk, (b, s, h, d), jnp.float32)
    v = jax.random.normal(kv, (b, s, h, d), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    kwargs = dict(q_positions=pos, kv_positions=pos, causal=True,
                  q_block=16, kv_block=16, canonical_positions=True)
    out_forced = flash_attention(q, k, v, kernel_impl="pallas_tuned", **kwargs)
    out_jnp = flash_attention(q, k, v, kernel_impl="jnp", **kwargs)
    np.testing.assert_array_equal(np.asarray(out_forced), np.asarray(out_jnp))
    with pytest.raises(ValueError, match="kernel_impl"):
        flash_attention(q, k, v, kernel_impl="mosaic", **kwargs)


# ------------------------------------------------------- stream dispatch

@pytest.mark.parametrize("block_rows", [1, 4, 32])
def test_sc_stream_mul_block_rows_invariant(block_rows):
    from repro.kernels import ops, ref
    key = jax.random.PRNGKey(block_rows)
    x = jax.random.randint(key, (500,), 0, 256, dtype=jnp.int32)
    y = jax.random.randint(jax.random.fold_in(key, 1), (500,), 0, 256,
                           dtype=jnp.int32)
    out = ops.sc_stream_mul(x, y, bits=8, block_rows=block_rows)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(ref.sc_stream_mul_ref(x, y, bits=8)))


def test_sc_stream_mul_tuned(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "tune.json"))
    from repro.kernels import ops, ref
    key = jax.random.PRNGKey(9)
    x = jax.random.randint(key, (700,), 0, 256, dtype=jnp.int32)
    y = jax.random.randint(jax.random.fold_in(key, 1), (700,), 0, 256,
                           dtype=jnp.int32)
    out = ops.sc_stream_mul(x, y, bits=8, tune=True)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(ref.sc_stream_mul_ref(x, y, bits=8)))
    doc = json.loads((tmp_path / "tune.json").read_text())
    assert any(k.startswith("sc_stream:") for k in doc["entries"])
