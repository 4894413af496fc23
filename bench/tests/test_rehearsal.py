"""A rehearsal of a whole run on the CPU at a tiny width: the traffic drives
``Engine.submit``/``Engine.step``, the metric arithmetic runs, and the
reference decides ``correct``. Then the same run with the timed path
broken underneath, which ``correct`` has to catch. Nothing here is a device
number: the peaks passed in are placeholders and no result is printed.

    PYTHONPATH=src:. JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import copy
import time

import jax
import numpy as np
import pytest

from bench import cell

TINY = {
    "name": "tiny", "arch": "smollm-360m", "reference": "llama",
    "model": {"num_hidden_layers": 2, "hidden_size": 64,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "intermediate_size": 128, "vocab_size": 256,
              "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
              "tie_word_embeddings": True, "hidden_act": "silu",
              "torch_dtype": "bfloat16"},
    "derived": {"head_dim": 16},
    "numerics": {"projections": "exact", "attention": "exact"},
    # bf16 serving against the f32 reference: near-ties flip, by a few
    # hundredths of a logit at this width; a wrong token lies O(1) below.
    "limits": {"max_logit_gap": 0.5},
    "control": {"numerics": "fp8_weights"},
}
SC = {**TINY, "numerics": {"projections": "sc", "sc_bits": 8,
                           "sc_impl": "auto", "attention": "exact"},
      "control": {"numerics": "sc", "sc_bits": 4}}
ENGINE = {"capacity": 4, "block": 8, "chunk": 16, "max_seq": 96,
          "prefix_cache": True}
CLOSED = {"name": "tiny-closed", "loop": "closed", "requests": 40,
          "shape_seed": 1, "engine": ENGINE, "drain_s": 30,
          "prompt": {"dist": "uniform", "low": 16, "high": 48},
          "output": {"dist": "uniform", "low": 8, "high": 24},
          "sample_tokens": 40,
          "trace_s": 1.0, "fill_s": 5.0}
OPEN = {"name": "tiny-open", "loop": "open", "shape_seed": 2,
        "engine": ENGINE, "drain_s": 30,
        "arrivals": {"process": "poisson", "rate_per_s": 4.0,
                     "preroll_s": 0.5, "tail_s": 1.0},
        "system_prompts": {"count": 2, "tokens": 16, "zipf_s": 1.0},
        "prompt": {"dist": "lognormal", "median": 16, "sigma": 0.5,
                   "low": 8, "high": 32},
        "output": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                   "low": 4, "high": 24},
        "sample_tokens": 40, "trace_s": 1.0}
PEAK = {"bf16_flops_per_s": 1e12, "int8_ops_per_s": 2e12,
        "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9}
E2E = [{"name": n, "unit": u} for n, u in
       (("tokens_per_s", "tokens/s"), ("itl_p95_ms", "ms"),
        ("ttft_p95_ms", "ms"), ("setup_s", "s"))]


#: Limits on the mean gap for the control test: at these widths the program
#: reads a third of them or less, and its control ten times or more.
MEAN_LIMIT = {"exact": 0.002, "sc": 0.05}


def spec(mix, config=TINY):
    return cell.CellSpec(name=mix["name"], chips=1, config=config, mix=mix,
                         end_to_end=E2E, per_layer=[])


def execute(mix, seed=5, seconds=2.0, config=TINY):
    return cell.execute(spec(mix, config), seed=seed, seconds=seconds,
                        trace=False, devices=jax.devices(), peak=PEAK,
                        t_process=time.perf_counter(), say=lambda m: None)


@pytest.mark.parametrize("mix", [CLOSED, OPEN], ids=["closed", "open"])
def test_sound_run_is_correct(mix):
    r = execute(mix)
    assert r["correct"], r["compared"]
    assert list(r)[-1] == "compared"
    m = r["metrics"]
    assert m["tokens_per_s"]["value"] > 0 and m["itl_p95_ms"]["value"] > 0
    assert m["setup_s"]["value"] > 0
    assert ("ttft_p95_ms" in m) == (mix["loop"] == "open")
    assert r["compared"]["max_logit_gap"]["value"] < 0.5
    assert r["attempted"] > 0 and r["failed"] == 0


def test_limits_choose_the_compared_numbers():
    config = copy.deepcopy(TINY)
    config["limits"] = {"mean_logit_gap": 0.2, "mismatch_share": 50.0}
    r = execute(CLOSED, config=config)
    assert r["correct"], r["compared"]
    assert "max_logit_gap" not in r["compared"]
    assert set(r["compared"]) >= {"mean_logit_gap", "mismatch_share"}


def test_sc_numerics_run_is_correct():
    r = execute(CLOSED, config=SC)
    assert r["correct"], r["compared"]


def test_altered_token_is_caught(monkeypatch):
    """A token altered where it is produced: every third sampled token is
    replaced by the row's least likely one."""
    from repro.serving.engine import Engine
    real = Engine._sample
    count = [0]

    def altered(self, entry, row):
        tok = real(self, entry, row)
        count[0] += 1
        if count[0] % 3 == 0:
            return np.argmin(row, axis=-1).astype(np.int32)
        return tok

    monkeypatch.setattr(Engine, "_sample", altered)
    r = execute(CLOSED, seed=6)
    assert not r["correct"]
    assert r["compared"]["max_logit_gap"]["value"] > 0.5


def test_stale_cache_is_caught(monkeypatch):
    """A decode step that returns the cache it was given: the state the
    next token needs is never written."""
    from repro.launch import steps
    from repro.serving import engine as engine_mod
    real = steps.cached_paged_decode_step

    def stale(*a, **k):
        fn, sh, ab = real(*a, **k)

        def decode(params, cache, tables, batch):
            logits, _ = fn(params, jax.tree.map(lambda x: x.copy(), cache),
                           tables, batch)
            return logits, cache
        return decode, sh, ab

    monkeypatch.setattr(engine_mod, "cached_paged_decode_step", stale)
    r = execute(OPEN, seed=7)
    assert not r["correct"]


def test_half_the_batch_left_out_is_caught(monkeypatch):
    """A decode step that computes only the first half of its slots and
    hands the other half the same rows (a closed queue keeps every slot
    busy)."""
    from repro.launch import steps
    from repro.serving import engine as engine_mod
    real = steps.cached_paged_decode_step

    def halved(*a, **k):
        fn, sh, ab = real(*a, **k)

        def decode(params, cache, tables, batch):
            logits, cache = fn(params, cache, tables, batch)
            half = logits.shape[0] // 2
            return logits.at[half:2 * half].set(logits[:half]), cache
        return decode, sh, ab

    monkeypatch.setattr(engine_mod, "cached_paged_decode_step", halved)
    r = execute(CLOSED, seed=9)
    assert not r["correct"]


@pytest.mark.parametrize("numerics", ["exact", "sc"])
def test_control_reads_above_the_program(numerics):
    """The control (the configuration's ``control`` group: the reference
    one precision step below the configuration's, put in the program's
    place) on the same served tokens, judged by the same comparison as the
    program: the program passes, the control does not."""
    config = copy.deepcopy(TINY if numerics == "exact" else SC)
    if numerics == "exact":     # wide enough for near-ties at e4m3 weights
        config["model"].update(vocab_size=4096, hidden_size=128,
                               intermediate_size=256)
        config["derived"] = {"head_dim": 32}
    config["limits"] = {"mean_logit_gap": MEAN_LIMIT[numerics]}
    r = cell.execute(spec(CLOSED, config), seed=8, seconds=2.0, trace=False,
                     devices=jax.devices(), peak=PEAK,
                     t_process=time.perf_counter(), say=lambda m: None,
                     control=True)
    print(f"\n{numerics}: program {r['program']}, control {r['control']}")
    assert r["program"]["correct"] and r["correct"]
    assert not r["control"]["correct"]
    assert r["control"]["mean_logit_gap"] > 3 * r["program"]["mean_logit_gap"]
