"""The plain reference against the program, at a reduced width on the CPU.

    PYTHONPATH=src:. JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import llama

MODEL = {"num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 2, "intermediate_size": 128,
         "vocab_size": 256, "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
         "tie_word_embeddings": True, "hidden_act": "silu"}
SIZES = llama.Sizes(MODEL, {"head_dim": 16})


def program_cfg(**over):
    from repro.configs.registry import ARCHS
    return dataclasses.replace(
        ARCHS["smollm-360m"], n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=2, head_dim=16, d_ff=128, vocab_size=256,
        dtype="float32", norm_eps=1e-5, **over).validate()


def test_param_tree_matches_program_layout():
    from repro.models import bind
    want = jax.eval_shape(bind(program_cfg()).init_params,
                          jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda: llama.make_params(SIZES, 7, jnp.float32))
    assert (jax.tree.structure(got) == jax.tree.structure(want))
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.shape == w.shape


def test_same_seed_same_weights_large_seed():
    a = llama.make_params(SIZES, 2 ** 31 + 12345)
    b = llama.make_params(SIZES, 2 ** 31 + 12345)
    c = llama.make_params(SIZES, 2 ** 31 + 12346)
    assert all(np.array_equal(x, y) for x, y in
               zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    assert not np.array_equal(a["embed"], c["embed"])


# f32 on both sides, different summation orders (flash blocks, grouped
# einsums): agreement to a few float32 ulps of the unit-scale logits.
EXACT_TOL = 2e-4


def test_exact_forward_equals_program_prefill():
    from repro.models import bind
    cfg = program_cfg()
    params = llama.make_params(SIZES, 3, jnp.float32)
    tokens = np.random.default_rng(0).integers(0, 256, (1, 24)).astype(np.int32)
    want, _ = jax.jit(bind(cfg).prefill_step)(params, {"tokens": tokens})
    with jax.default_matmul_precision("highest"):
        got = llama.logits_at(params, tokens, np.array([[23]]), SIZES)
    np.testing.assert_allclose(np.asarray(got)[0, 0], np.asarray(want)[0, -1],
                               atol=EXACT_TOL, rtol=0)


def test_sc_forward_equals_program_prefill():
    """Same weights through the program's SC-GEMM path and the reference's
    closed form: f32 activations on both sides, so quantization levels agree
    and only summation order differs."""
    from repro.models import bind
    cfg = program_cfg(use_sc_gemm=True, sc_bits=8, sc_impl="ref")
    params = llama.make_params(SIZES, 4, jnp.float32)
    tokens = np.random.default_rng(1).integers(0, 256, (1, 16)).astype(np.int32)
    want, _ = jax.jit(bind(cfg).prefill_step)(params, {"tokens": tokens})
    got = llama.logits_at(params, tokens, np.array([[15]]), SIZES,
                          numerics="sc", bits=8)
    np.testing.assert_allclose(np.asarray(got)[0, 0], np.asarray(want)[0, -1],
                               atol=EXACT_TOL, rtol=0)


@pytest.mark.parametrize("bits", [4, 8])
@pytest.mark.parametrize("shape", [(5, 37, 11), (16, 128, 24)])
def test_closed_form_counts_equal_program_reference(bits, shape):
    from repro.core.sc_matmul import sc_matmul_reference
    from repro.core.sc_numerics import recover_counts
    m, k, n = shape
    ka, kb = jax.random.split(jax.random.PRNGKey(m + k + n + bits))
    a = jax.random.normal(ka, (m, k), jnp.float32)
    b = jax.random.normal(kb, (k, n), jnp.float32) * k ** -0.5
    sx, mx, _ = llama.quantize(a, bits, axis=-1)
    sy, my, _ = llama.quantize(b, bits, axis=None)
    pairwise = np.asarray(llama.sc_counts_pairwise(sx, mx, sy, my, bits))
    matmuls = np.asarray(llama.sc_counts(sx, mx, sy, my, bits))
    want = recover_counts(sc_matmul_reference(a, b, bits=bits, k_block=16,
                                              row_quant=True),
                          a, b, bits=bits, row_quant=True)
    np.testing.assert_array_equal(pairwise, want)
    np.testing.assert_array_equal(matmuls, want)


def test_fp8_control_differs_from_exact():
    """The bfloat16 control rounds the weights to e4m3 with a scale per
    output channel: the logits move, by a little."""
    params = llama.make_params(SIZES, 5, jnp.float32)
    tokens = np.arange(12, dtype=np.int32)[None]
    rows = np.array([[11]])
    with jax.default_matmul_precision("highest"):
        exact = llama.logits_at(params, tokens, rows, SIZES)
        ctrl = llama.logits_at(params, tokens, rows, SIZES,
                               numerics="fp8_weights")
    gap = float(jnp.abs(exact - ctrl).max())
    assert 1e-4 < gap < 0.5
