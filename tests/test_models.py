"""Model-substrate numerics: flash attention vs naive, SSD vs naive recurrence,
and prefill/decode consistency for every family."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.models import bind
from repro.models.layers import decode_attention, flash_attention
from repro.models.mamba2 import ssd_scan


# ----------------------------------------------------------- flash attention

def _naive_attention(q, k, v, *, causal=True, window=None, softcap=None):
    b, sq, h, d = q.shape
    _, skv, kv, _ = k.shape
    g = h // kv
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / d ** 0.5
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    qa = jnp.arange(sq)[:, None]
    ka = jnp.arange(skv)[None, :]
    mask = jnp.ones((sq, skv), bool)
    if causal:
        mask &= qa >= ka
    if window is not None:
        mask &= (qa - ka) < window
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("window", [None, 16])
@pytest.mark.parametrize("softcap", [None, 20.0])
def test_flash_matches_naive(window, softcap):
    key = jax.random.PRNGKey(0)
    b, s, h, kv, d = 2, 96, 4, 2, 16
    kq, kk, kv_ = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, h, d), jnp.float32)
    k = jax.random.normal(kk, (b, s, kv, d), jnp.float32)
    v = jax.random.normal(kv_, (b, s, kv, d), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    out = flash_attention(q, k, v, q_positions=pos, kv_positions=pos,
                          causal=True, window=window, logit_softcap=softcap,
                          q_block=32, kv_block=32)
    ref = _naive_attention(q, k, v, causal=True, window=window, softcap=softcap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_flash_skip_masked_blocks_identical():
    """§Perf triangular schedule must be numerically identical to full sweep."""
    key = jax.random.PRNGKey(1)
    b, s, h, kv, d = 1, 128, 4, 4, 16
    q, k, v = (jax.random.normal(kk, (b, s, hh, d), jnp.float32)
               for kk, hh in zip(jax.random.split(key, 3), (h, kv, kv)))
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    kw = dict(q_positions=pos, kv_positions=pos, causal=True,
              q_block=32, kv_block=32)
    full = flash_attention(q, k, v, skip_masked_blocks=False, **kw)
    tri = flash_attention(q, k, v, skip_masked_blocks=True, **kw)
    np.testing.assert_allclose(np.asarray(tri), np.asarray(full), rtol=1e-6, atol=1e-6)


def test_decode_matches_prefill_last_token():
    key = jax.random.PRNGKey(2)
    b, s, h, kv, d = 2, 40, 4, 2, 16
    q, k, v = (jax.random.normal(kk, (b, s, hh, d), jnp.float32)
               for kk, hh in zip(jax.random.split(key, 3), (h, kv, kv)))
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    full = flash_attention(q, k, v, q_positions=pos, kv_positions=pos,
                           causal=True, q_block=8, kv_block=8)
    # decode: same last query against the cache (padded to longer max_seq)
    k_cache = jnp.pad(k, ((0, 0), (0, 24), (0, 0), (0, 0)))
    v_cache = jnp.pad(v, ((0, 0), (0, 24), (0, 0), (0, 0)))
    out = decode_attention(q[:, -1:], k_cache, v_cache,
                           q_position=jnp.full((b,), s - 1, jnp.int32))
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(full[:, -1]),
                               rtol=2e-5, atol=2e-5)


# --------------------------------------------------------------------- SSD

def _naive_ssm(x, dt, a_log, bmat, cmat):
    """Direct recurrence h' = exp(-dt·a)h + dt·x⊗B ; y = h·C."""
    b, l, h, p = x.shape
    n = bmat.shape[-1]
    state = jnp.zeros((b, h, p, n))
    ys = []
    for t in range(l):
        da = jnp.exp(-(dt[:, t] * a_log[None, :]))            # (B, H)
        upd = (dt[:, t, :, None] * x[:, t])[..., None] * bmat[:, t, None, None, :]
        state = state * da[..., None, None] + upd
        ys.append(jnp.einsum("bhpn,bn->bhp", state, cmat[:, t]))
    return jnp.stack(ys, axis=1), state


def test_ssd_scan_matches_naive_recurrence():
    key = jax.random.PRNGKey(3)
    b, l, h, p, n, chunk = 2, 32, 3, 8, 4, 8
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (b, l, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h)))
    a_log = jnp.abs(jax.random.normal(ks[2], (h,))) + 0.5
    bmat = jax.random.normal(ks[3], (b, l, n))
    cmat = jax.random.normal(jax.random.fold_in(key, 9), (b, l, n))
    y, final = ssd_scan(x, dt, a_log, bmat, cmat, chunk)
    y_ref, final_ref = _naive_ssm(x, dt, a_log, bmat, cmat)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(final), np.asarray(final_ref),
                               rtol=1e-4, atol=1e-4)


# ------------------------------------------------- prefill/decode consistency

def _tiny(family, **kw):
    base = dict(name=f"t-{family}", family=family, n_layers=2, d_model=64,
                n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128, vocab_size=128,
                dtype="float32", q_block=16, kv_block=16, loss_chunk=16,
                remat=False)
    base.update(kw)
    return ModelConfig(**base).validate()


@pytest.mark.parametrize("cfg", [
    _tiny("dense"),
    _tiny("dense", windows=(8, None), attn_softcap=30.0, post_norms=True,
          norm_plus_one=True, n_layers=4),
    _tiny("moe", d_ff=0, n_experts=4, top_k=2, moe_d_ff=32, moe_flags=(True,)),
    _tiny("ssm", ssm_state=16, ssm_headdim=16, ssm_chunk=4, n_kv_heads=1),
    _tiny("hybrid", ssm_state=16, ssm_headdim=16, ssm_chunk=4,
          shared_attn_every=2, n_kv_heads=4, n_layers=4),
], ids=lambda c: c.name + c.family)
def test_decode_consistent_with_prefill(cfg):
    """Token-by-token decode reproduces the full-sequence forward logits."""
    m = bind(cfg)
    key = jax.random.PRNGKey(0)
    params = m.init_params(key)
    b, s = 2, 16
    tokens = jax.random.randint(jax.random.fold_in(key, 1), (b, s), 0,
                                cfg.vocab_size, dtype=jnp.int32)

    hidden, _ = m.forward_hidden(params, {"tokens": tokens})
    if cfg.family == "ssm":
        full_logits = hidden @ params["embed"].T
    elif cfg.family == "hybrid":
        full_logits = hidden @ params["lm_head"]
    else:
        from repro.models.transformer import logits_from_hidden
        full_logits = logits_from_hidden(params, cfg, hidden)

    cache = m.init_cache(b, s)
    outs = []
    for t in range(s):
        logits, cache = m.decode_step(params, cache, {"tokens": tokens[:, t:t + 1]})
        outs.append(logits[:, 0])
    decoded = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(decoded), np.asarray(full_logits),
                               rtol=2e-3, atol=2e-3)


def test_moe_capacity_drops_are_bounded():
    """Routing is dropless: every token is computed by each of its top-k
    experts (there is no capacity to overflow), so a token's output does
    not depend on the tokens it is batched with."""
    from repro.models.moe import moe_ffn, init_moe_params
    cfg = _tiny("moe", d_ff=0, n_experts=4, top_k=2, moe_d_ff=32,
                moe_flags=(True,))
    key = jax.random.PRNGKey(0)
    params = init_moe_params(cfg, key, jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 32, cfg.d_model))
    out, aux, stats = moe_ffn(params, x, cfg)
    assert out.shape == x.shape
    assert float(aux) > 0.5  # balance loss is ~1 at uniform routing
    assert bool(jnp.all(jnp.isfinite(out)))
    assert int(stats[0]) == 2 * 32 * cfg.top_k      # no row dropped
    alone, _, _ = moe_ffn(params, x[1:, 5:6], cfg)
    np.testing.assert_allclose(np.asarray(alone[0, 0]),
                               np.asarray(out[1, 5]), rtol=1e-5, atol=1e-6)
