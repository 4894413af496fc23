"""Per-assigned-architecture smoke tests: a REDUCED config of the same family
runs one forward/train step and one decode step on CPU — output shapes check
out and nothing is NaN. Full configs are exercised only by the dry-run."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs.registry import ARCHS
from repro.models import bind


def _batch_for(cfg, b=2, s=32):
    tok_shape = (b, s, cfg.n_codebooks) if cfg.n_codebooks else (b, s)
    key = jax.random.PRNGKey(7)
    batch = {
        "tokens": jax.random.randint(key, tok_shape, 0, cfg.vocab_size, dtype=jnp.int32),
        "labels": jax.random.randint(key, tok_shape, 0, cfg.vocab_size, dtype=jnp.int32),
    }
    if cfg.family == "vlm":
        batch["visual_embeds"] = jnp.ones((b, 8, cfg.d_model), jnp.float32)
        batch["mrope_positions"] = jnp.broadcast_to(
            jnp.arange(s, dtype=jnp.int32), (3, b, s))
    return batch


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_reduced_train_step(arch):
    cfg = ARCHS[arch].reduced(dtype="float32")
    m = bind(cfg)
    params = m.init_params(jax.random.PRNGKey(0))
    batch = _batch_for(cfg)

    loss, grads = jax.value_and_grad(lambda p: m.loss_fn(p, batch))(params)
    assert jnp.isfinite(loss), f"{arch}: loss {loss}"
    assert float(loss) > 0
    finite = jax.tree.map(lambda g: bool(jnp.all(jnp.isfinite(g))), grads)
    assert all(jax.tree.leaves(finite)), f"{arch}: non-finite grads"
    nonzero = sum(float(jnp.abs(g).sum()) for g in jax.tree.leaves(grads))
    assert nonzero > 0, f"{arch}: all-zero grads"


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_reduced_decode_step(arch):
    cfg = ARCHS[arch].reduced(dtype="float32")
    m = bind(cfg)
    params = m.init_params(jax.random.PRNGKey(1))
    b, max_seq = 2, 16
    cache = m.init_cache(b, max_seq)
    tok_shape = (b, 1, cfg.n_codebooks) if cfg.n_codebooks else (b, 1)
    batch = {"tokens": jnp.zeros(tok_shape, jnp.int32)}
    logits, cache2 = m.decode_step(params, cache, batch)
    if cfg.n_codebooks:
        assert logits.shape == (b, 1, cfg.n_codebooks, cfg.vocab_size)
    else:
        assert logits.shape == (b, 1, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits))), arch
    # every sequence's cache position advanced (pos is per-sequence (B,))
    pos = cache2.pos if hasattr(cache2, "pos") else None
    assert pos is None or bool(jnp.all(pos == 1))


def test_all_archs_present():
    assert len(ARCHS) == 11
    families = {cfg.family for cfg in ARCHS.values()}
    assert families == {"dense", "moe", "ssm", "hybrid", "vlm", "audio"}
