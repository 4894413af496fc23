"""deepseek-v2-lite on the CPU at a tiny size with the same layer kinds (one
dense layer, then two MoE layers of 8 experts, top 2, one shared expert;
latent rank 32, rope 16, nope 16, v 16; YaRN rope): the serving engine's
programs against the plain reference ``bench/reference/deepseek_v2.py``,
dropless routing, the YaRN constants, the device's expert-load counters,
prefix sharing, and the grouped matmul's two implementations."""
import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import deepseek_v2 as ref
from repro.configs.registry import ARCHS
from repro.models import bind
from repro.serving import Engine, Request

MODEL = {
    "num_hidden_layers": 3, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 4, "intermediate_size": 128, "vocab_size": 256,
    "rms_norm_eps": 1e-6, "rope_theta": 10000, "tie_word_embeddings": False,
    "hidden_act": "silu", "kv_lora_rank": 32, "q_lora_rank": None,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 16, "v_head_dim": 16,
    "n_routed_experts": 8, "num_experts_per_tok": 2, "n_shared_experts": 1,
    "moe_intermediate_size": 32, "first_k_dense_replace": 1,
    "moe_layer_freq": 1, "norm_topk_prob": False,
    "routed_scaling_factor": 1, "scoring_func": "softmax",
    "topk_method": "greedy",
    "rope_scaling": {"type": "yarn", "factor": 40,
                     "original_max_position_embeddings": 4096,
                     "beta_fast": 32, "beta_slow": 1, "mscale": 0.707,
                     "mscale_all_dim": 0.707}}
SIZES = ref.Sizes(MODEL, {"head_dim": 32})

#: Largest |program − reference| logit allowed, on logits of standard
#: deviation about 1. ``exact``: the program is float32 here, the reference
#: float32 at ``highest`` precision; they differ by the order of their sums
#: (the absorbed attention reassociates the latent products), under 1e-5,
#: while weights rounded to float8 e4m3 (the control) move the logits by
#: 0.4 to 0.7. ``sc``: both sides quantize the same operands, and they
#: agree to 1e-6 until a float rounding (chunked prefill against the full
#: forward) lands on the other side of a quantization step, which moves
#: that request's logits by up to 0.15 at this width; the float8 control
#: misses the SC reference by 4.5 to 5.5. The test checks that the control
#: fails each limit.
TOL = {"exact": 1e-3, "sc": 1.0}


def program_cfg(**over):
    return dataclasses.replace(
        ARCHS["deepseek-v2-lite"].reduced(dtype="float32"), **over).validate()


@pytest.fixture(scope="module")
def params():
    return ref.make_params(SIZES, 5, jnp.float32)


class _Recorder(Engine):
    """An engine that keeps the logit row behind every token it emits."""

    def __init__(self, *a, **k):
        super().__init__(*a, **k)
        self.logits: dict[str, list] = {}

    def _sample(self, entry, row):
        self.logits.setdefault(entry.request.uid, []).append(np.asarray(row))
        return super()._sample(entry, row)


def _serve(cfg, params, prompts, *, max_new=6, capacity=4, prefix=False):
    eng = _Recorder(cfg, params, capacity=capacity, max_seq=64, block=8,
                    chunk=8, prefix_cache=prefix)
    reqs = [Request(uid=f"r{i}", prompt=p, max_new_tokens=max_new)
            for i, p in enumerate(prompts)]
    out = {r.uid: r for r in eng.run(reqs)}
    return eng, out


def _prompts(n, seed=11, lengths=(13, 20, 9, 16, 11, 18, 7, 15)):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, MODEL["vocab_size"], lengths[i % len(lengths)],
                         dtype=np.int32) for i in range(n)]


def _reference(params, prompts, results, numerics):
    """The reference's logits at every served position, teacher-forced on
    the served tokens, padded to one block."""
    s = max(len(p) + len(results[f"r{i}"].tokens) for i, p in
            enumerate(prompts))
    n = max(len(r.tokens) for r in results.values())
    toks = np.zeros((len(prompts), s), np.int32)
    rows = np.zeros((len(prompts), n), np.int32)
    for i, p in enumerate(prompts):
        served = results[f"r{i}"].tokens
        seq = np.concatenate([p, served[:-1]])
        toks[i, :len(seq)] = seq
        rows[i, :len(served)] = np.arange(len(p) - 1, len(p) - 1 + len(served))
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.logits_at(params, toks, rows, SIZES,
                                        numerics=numerics))


def test_config_file_keys_agree():
    """The benchmark's configuration file states the published config.json
    twice: its keys at the top level, as published but for ``reduced``, and
    under ``model``, the copy the harness runs. Both copies agree, every
    key that differs from ``published`` is in ``reduced``, and the
    program's registry entry has the published widths."""
    path = Path(ref.__file__).parents[1] / "configs" / \
        "deepseek-v2-lite-bf16.json"
    conf = json.loads(path.read_text())
    model = conf["model"]
    mirrored = set(model) - {"architectures", "torch_dtype"}
    assert mirrored <= set(conf)
    assert {k: conf[k] for k in mirrored} == {k: model[k] for k in mirrored}
    assert all(conf[k] != v for k, v in conf["published"].items())
    assert sorted(conf["published"]) == sorted(conf["reduced"])
    full = ARCHS["deepseek-v2-lite"]
    assert (full.n_layers, full.d_model, full.n_heads, full.kv_lora_rank,
            full.qk_nope_head_dim, full.qk_rope_head_dim, full.v_head_dim,
            full.d_ff, full.moe_d_ff, full.n_experts, full.top_k,
            full.first_k_dense, full.vocab_size) == (
        conf["published"]["num_hidden_layers"], conf["hidden_size"],
        conf["num_attention_heads"], conf["kv_lora_rank"],
        conf["qk_nope_head_dim"], conf["qk_rope_head_dim"],
        conf["v_head_dim"], conf["intermediate_size"],
        conf["moe_intermediate_size"], conf["n_routed_experts"],
        conf["num_experts_per_tok"], conf["first_k_dense_replace"],
        conf["vocab_size"])


def test_param_tree_matches_program_layout(params):
    want = jax.eval_shape(bind(program_cfg()).init_params,
                          jax.random.PRNGKey(0))
    assert jax.tree.structure(params) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(params), jax.tree.leaves(want)):
        assert g.shape == w.shape


@pytest.mark.parametrize("numerics", ["exact", "sc"])
def test_engine_logits_match_reference(params, numerics):
    """Chunked prefill, then paged decode through the engine's programs,
    against the reference's full forward, at every served position; the
    float8-weight control fails the same limit."""
    cfg = program_cfg(use_sc_gemm=numerics == "sc")
    prompts = _prompts(3)
    eng, results = _serve(cfg, params, prompts)
    want = _reference(params, prompts, results, numerics)
    ctrl = _reference(params, prompts, results, "fp8_weights")
    gap = ctrl_gap = 0.0
    for i in range(len(prompts)):
        got = np.stack(eng.logits[f"r{i}"])
        n = len(got)
        gap = max(gap, float(np.abs(got - want[i, :n]).max()))
        ctrl_gap = max(ctrl_gap, float(np.abs(ctrl[i, :n] - want[i, :n]).max()))
    assert gap < TOL[numerics], gap
    assert ctrl_gap > TOL[numerics], ctrl_gap


@pytest.mark.parametrize("numerics", ["exact", "sc"])
def test_dropless_routing_is_batch_invariant(params, numerics):
    """A request's logits alone equal its logits batched with 7 others: no
    expert capacity, so no neighbour can push its rows out."""
    cfg = program_cfg(use_sc_gemm=numerics == "sc")
    prompts = _prompts(8, seed=3)
    alone, _ = _serve(cfg, params, prompts[:1], capacity=1)
    batched, _ = _serve(cfg, params, prompts, capacity=8)
    np.testing.assert_allclose(np.stack(alone.logits["r0"]),
                               np.stack(batched.logits["r0"]),
                               rtol=0, atol=1e-5)


@pytest.mark.parametrize("side", ["program", "reference"])
def test_yarn_constants(side):
    """mscale(40, 0.707) = 0.1 · 0.707 · ln 40 + 1, its square scales the
    softmax, and the ramp runs between dims 10 and 23 of 32 (the correction
    dims of beta_fast 32 and beta_slow 1 over 4096 positions)."""
    if side == "program":
        from repro.models.layers import (rope_inv_freq, yarn_correction_range,
                                         yarn_mscale)
        from repro.models.transformer import mla_softmax_scale
        cfg = ARCHS["deepseek-v2-lite"]
        m = yarn_mscale(40, 0.707)
        lo_hi = yarn_correction_range(64, 10000, 4096, 32, 1)
        inv = rope_inv_freq(64, 10000, yarn_factor=40)
        scale = mla_softmax_scale(cfg)
    else:
        m = ref.yarn_mscale(40, 0.707)
        lo_hi = ref.yarn_correction_range(64, 10000, 4096, 32, 1)
        inv = ref.yarn_inv_freq(64, 10000, (40, 4096, 32, 1, 0.707, 0.707))
        scale = None
    assert m == pytest.approx(1.26080, abs=5e-6)
    assert m * m == pytest.approx(1.58962, abs=1e-5)
    assert lo_hi == (10, 23)
    f = 1.0 / 10000 ** (np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:10], f[:10], rtol=1e-6)       # original
    np.testing.assert_allclose(inv[23:], f[23:] / 40, rtol=1e-6)   # / factor
    if scale is not None:
        assert scale == pytest.approx(192 ** -0.5 * m * m, rel=1e-9)


def test_expert_load_counters_match_host_recount(params, monkeypatch):
    """One decode step: the counters the step program keeps on the device
    equal a recount, on the host, of the routing of its rows (every slot
    live): (token, expert) rows, and the busiest expert's rows summed over
    the MoE layers."""
    from repro.models import moe
    cfg = program_cfg()
    prompts = _prompts(4, seed=21)
    eng = Engine(cfg, params, capacity=4, max_seq=64, block=8, chunk=8,
                 prefix_cache=False)
    for i, p in enumerate(prompts):
        eng.submit(Request(uid=f"r{i}", prompt=p, max_new_tokens=30))
    while eng.queue or eng._staging is not None:
        eng.step()
    assert len(eng.pool.entries) == 4
    c0 = eng.counters()
    rows = {s: np.concatenate([e.request.prompt,
                               np.asarray(e.generated, np.int32)])
            for s, e in eng.pool.entries.items()}
    eng.step()
    c1 = eng.counters()

    picked = []
    real = moe.route

    def record(p, x, c):
        out = real(p, x, c)
        jax.debug.callback(lambda idx: picked.append(np.asarray(idx)[-1]),
                           out[2], ordered=True)   # the decode row's experts
        return out
    monkeypatch.setattr(moe, "route", record)
    layers = [[] for _ in range(cfg.scan_layers)]
    for seq in rows.values():
        picked.clear()
        jax.block_until_ready(bind(cfg).forward_hidden(
            params, {"tokens": jnp.asarray(seq)[None]}))
        jax.effects_barrier()
        for li, experts in enumerate(picked):
            layers[li].append(experts)
    n_moe = cfg.scan_layers
    assert c1["moe_routed_rows"] - c0["moe_routed_rows"] == \
        4 * cfg.top_k * n_moe
    busiest = sum(int(np.bincount(np.concatenate(chosen),
                                  minlength=cfg.n_experts).max())
                  for chosen in layers)
    assert c1["moe_max_expert_rows"] - c0["moe_max_expert_rows"] == busiest


def test_prefix_hit_gives_the_logits_of_a_miss(params):
    """The MoE family shares prompt prefixes: a request whose first 16
    tokens are already in the latent page pool gets the logits it gets in a
    fresh engine that computes them."""
    cfg = program_cfg()
    first, = _prompts(1, seed=8, lengths=(24,))
    second = np.concatenate([first[:16], _prompts(1, seed=9,
                                                  lengths=(7,))[0]])
    hit = _Recorder(cfg, params, capacity=2, max_seq=64, block=8, chunk=8)
    assert hit.prefix is not None
    hit.run([Request(uid="a", prompt=first, max_new_tokens=4)])
    hit.run([Request(uid="b", prompt=second, max_new_tokens=4)])
    assert hit.counters()["prefix_hits"] == 1
    assert hit.counters()["prefill_tokens_saved"] == 16
    miss = _Recorder(cfg, params, capacity=2, max_seq=64, block=8, chunk=8,
                     prefix_cache=False)
    miss.run([Request(uid="b", prompt=second, max_new_tokens=4)])
    np.testing.assert_array_equal(np.stack(hit.logits["b"]),
                                  np.stack(miss.logits["b"]))


@pytest.mark.parametrize("m,k,n,groups", [(40, 64, 32, 8), (200, 128, 96, 5)])
def test_grouped_matmul_kernel_matches_ragged_dot(m, k, n, groups):
    """The TPU kernel (interpreted here) and ``lax.ragged_dot`` give the same
    rows, empty groups and a row count off the kernel's tile included."""
    from repro.kernels.grouped_matmul import _gmm, _ragged
    from repro.kernels.ops import default_interpret
    key = jax.random.PRNGKey(m)
    lhs = jax.random.normal(key, (m, k), jnp.float32)
    rhs = jax.random.normal(jax.random.fold_in(key, 1), (groups, k, n),
                            jnp.float32)
    cuts = np.sort(np.random.default_rng(m).integers(0, m, groups - 1))
    sizes = jnp.asarray(np.diff(np.concatenate([[0], cuts, [m]])), jnp.int32)
    sizes = sizes.at[1].set(0).at[0].add(sizes[1])       # an empty group
    got = _gmm(lhs, rhs, sizes, interpret=default_interpret())
    want = _ragged(lhs, rhs, sizes)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-4)
