"""Prefill -> decode handoff: the filled cache must continue exactly where
token-by-token decoding would be, for every family with a cache."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.models import bind


def _cfg(family, **kw):
    base = dict(name=f"p-{family}", family=family, n_layers=2, d_model=64,
                n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128, vocab_size=128,
                dtype="float32", q_block=16, kv_block=16, loss_chunk=16,
                remat=False)
    base.update(kw)
    return ModelConfig(**base).validate()


CASES = [
    _cfg("dense"),
    _cfg("dense", qkv_bias=True, qk_norm=True),
    _cfg("audio", n_kv_heads=4, vocab_size=64, n_codebooks=4),
    _cfg("moe", d_ff=0, n_experts=4, top_k=2, moe_d_ff=32, moe_flags=(True,)),
    _cfg("ssm", n_kv_heads=1, d_ff=0, ssm_state=16, ssm_headdim=16, ssm_chunk=4),
    _cfg("hybrid", n_kv_heads=4, ssm_state=16, ssm_headdim=16, ssm_chunk=4,
         shared_attn_every=2, n_layers=4),
]


@pytest.mark.parametrize("cfg", CASES, ids=lambda c: c.name)
def test_prefill_matches_stepwise_decode(cfg):
    m = bind(cfg)
    key = jax.random.PRNGKey(0)
    params = m.init_params(key)
    b, s, extra = 2, 16, 4
    tok_shape = (b, s, cfg.n_codebooks) if cfg.n_codebooks else (b, s)
    tokens = jax.random.randint(jax.random.fold_in(key, 1), tok_shape, 0,
                                cfg.vocab_size, dtype=jnp.int32)

    cache = m.init_cache(b, s + extra)
    for t in range(s):
        step = tokens[:, t:t + 1]
        ref_logits, cache = m.decode_step(params, cache, {"tokens": step})

    pf_logits, pf_cache = m.prefill_step(params, {"tokens": tokens},
                                         extra_slots=extra)
    np.testing.assert_allclose(np.asarray(pf_logits), np.asarray(ref_logits),
                               rtol=2e-3, atol=2e-3)

    nxt = jnp.zeros((b, 1, cfg.n_codebooks) if cfg.n_codebooks else (b, 1),
                    jnp.int32)
    l_ref, _ = m.decode_step(params, cache, {"tokens": nxt})
    l_pf, _ = m.decode_step(params, pf_cache, {"tokens": nxt})
    np.testing.assert_allclose(np.asarray(l_pf), np.asarray(l_ref),
                               rtol=2e-3, atol=2e-3)


def test_sc_gemm_mode_trains():
    """use_sc_gemm: forward through the paper's numeric, STE gradients flow."""
    import dataclasses
    cfg = dataclasses.replace(_cfg("dense"), use_sc_gemm=True, sc_bits=8)
    m = bind(cfg)
    params = m.init_params(jax.random.PRNGKey(0))
    batch = {"tokens": jnp.ones((2, 16), jnp.int32),
             "labels": jnp.ones((2, 16), jnp.int32)}
    loss, grads = jax.value_and_grad(m.loss_fn)(params, batch)
    assert jnp.isfinite(loss)
    gsum = sum(float(jnp.abs(g).sum()) for g in jax.tree.leaves(grads))
    assert np.isfinite(gsum) and gsum > 0
