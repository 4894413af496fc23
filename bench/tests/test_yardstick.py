"""The yardstick's own arithmetic on the CPU: traffic, work functions,
peaks, readings and the trace reduction's interval logic.

    PYTHONPATH=src:. JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import math
import time
import types

import numpy as np
import pytest

from bench import readings, tracing, traffic, work
from bench.reference import llama

SMOLLM = llama.Sizes({"num_hidden_layers": 32, "hidden_size": 960,
                      "num_attention_heads": 15, "num_key_value_heads": 5,
                      "intermediate_size": 2560, "vocab_size": 49152,
                      "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
                      "tie_word_embeddings": True, "hidden_act": "silu"},
                     {"head_dim": 64})


@pytest.mark.parametrize("name", ["offline", "chat"])
def test_same_seed_same_requests(name):
    mix = traffic.load_mix(name)
    a, wa, _ = traffic.generate(mix, 2 ** 31 + 77, 30.0, 49152)
    b, wb, _ = traffic.generate(mix, 2 ** 31 + 77, 30.0, 49152)
    c, _, _ = traffic.generate(mix, 2 ** 31 + 78, 30.0, 49152)
    assert [s.uid for s in a] == [s.uid for s in b]
    assert all(np.array_equal(x.prompt, y.prompt) and x.max_new == y.max_new
               and x.arrival == y.arrival for x, y in zip(a, b))
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(wa, wb))
    # another seed: the same multiset of sizes, other tokens and order
    assert sorted((len(s.prompt), s.max_new) for s in a) == \
        sorted((len(s.prompt), s.max_new) for s in c)
    assert not all(np.array_equal(x.prompt[:8], y.prompt[:8])
                   for x, y in zip(a, c))


@pytest.mark.parametrize("name", ["offline", "chat"])
def test_lengths_within_the_mix(name):
    mix = traffic.load_mix(name)
    specs, warm, _ = traffic.generate(mix, 3, 30.0, 49152)
    eng = mix["engine"]
    sp = mix.get("system_prompts")
    base = sp["tokens"] if sp else 0
    for s in specs:
        user = len(s.prompt) - (base if s.system >= 0 else 0)
        assert mix["prompt"]["low"] <= user <= mix["prompt"]["high"]
        assert mix["output"]["low"] <= s.max_new <= mix["output"]["high"]
        assert len(s.prompt) + s.max_new <= eng["max_seq"]
    # warm-up covers every distinct prompt length the run sends
    assert {len(s.prompt) for s in specs} <= {len(s.prompt) for s in warm}


@pytest.mark.parametrize("seed", [11, 2 ** 33 + 5])
def test_every_seed_offers_the_same_work(seed):
    """Another seed: the same sizes, system prompts and arrival times, in
    the same order; other tokens. Lengths are not rounded to pages."""
    mix = traffic.load_mix("chat")
    a, _, _ = traffic.generate(mix, 1, 30.0, 49152)
    b, _, _ = traffic.generate(mix, seed, 30.0, 49152)
    assert [(len(s.prompt), s.max_new, s.system, s.arrival) for s in a] == \
        [(len(s.prompt), s.max_new, s.system, s.arrival) for s in b]
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert any(len(s.prompt) % mix["engine"]["block"] for s in a)


def test_gamma_arrivals_are_bursty_at_the_same_mean():
    rng = np.random.default_rng(0)
    poisson = traffic.gaps({"process": "poisson", "rate_per_s": 2.0},
                           20000, rng)
    gamma = traffic.gaps({"process": "gamma", "rate_per_s": 2.0, "cv": 3.0},
                         20000, rng)
    assert poisson.mean() == pytest.approx(0.5, rel=0.05)
    assert gamma.mean() == pytest.approx(0.5, rel=0.1)
    assert poisson.std() / poisson.mean() == pytest.approx(1.0, rel=0.05)
    assert gamma.std() / gamma.mean() == pytest.approx(3.0, rel=0.15)


def test_open_loop_is_a_schedule():
    mix = traffic.load_mix("chat")
    specs, _, _ = traffic.generate(mix, 5, 30.0, 49152)
    t = np.array([s.arrival for s in specs])
    assert np.all(np.diff(t) >= 0)
    arr = mix["arrivals"]
    assert t[0] == pytest.approx(-arr["preroll_s"])
    inside = ((t >= 0) & (t < 30.0)).sum()
    assert abs(inside / 30.0 - arr["rate_per_s"]) < 0.35 * arr["rate_per_s"]
    # zipf over the system prompts: the first is the most popular
    counts = np.bincount([s.system for s in specs], minlength=8)
    assert counts[0] == counts.max()


def test_peaks_table_known_and_unknown():
    p = work.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("cpu")


def test_model_flops_by_hand():
    s = SMOLLM
    per_layer = (960 * 960 + 2 * 960 * 320 + 960 * 960 + 3 * 960 * 2560)
    assert work.projection_params(s) == 32 * per_layer
    # one decode row at position 99 (context 100), sampled
    want = 2 * 32 * per_layer + 4 * 32 * 15 * 64 * 100 + 2 * 960 * 49152
    assert work.row_flops(s, 100, True) == want
    assert work.request_flops(s, 99, 100, 1) == want
    # a 3-row prompt, last row sampled: contexts 1, 2, 3
    rows = sum(work.row_flops(s, c, False) for c in (1, 2, 3)) + 2 * 960 * 49152
    assert work.request_flops(s, 0, 3, 1) == rows


def test_sc_call_cost_and_least_time():
    ops, nbytes = work.sc_call_cost(128, 960, 2560, 8)
    assert ops == 2 * 128 * 960 * 2560
    assert nbytes == 128 * 960 + 960 * 2560 + 128 * 2560 * 2
    p = work.peaks("TPU v5 lite")
    t = work.sc_least_seconds([(128, 960, 2560, 3)], 8, p)
    assert t == pytest.approx(3 * max(ops / 393e12, nbytes / 819e9))
    # decode rows at M = 32 are bound by reading the weights
    assert work.sc_least_seconds([(32, 960, 2560, 1)], 8, p) == \
        pytest.approx((32 * 960 + 960 * 2560 + 32 * 2560 * 2) / 819e9)


def test_kv_and_gathered_view_bytes():
    assert work.kv_bytes_per_token(SMOLLM) == 40960
    assert work.gathered_view_bytes(SMOLLM, 32, 32, 64) == 32 * 2048 * 40960


def test_union_of_intervals():
    total, merged = tracing.union_seconds([(0, 10), (5, 20), (30, 40),
                                           (40, 41), (35, 36)])
    assert merged == [[0, 20], [30, 41]]
    assert total == pytest.approx(31e-9)


def _run(times, seconds=1.0, start=10.0, mix_loop="open", specs=()):
    w = types.SimpleNamespace(start=start, end=start + seconds,
                              seconds=seconds, specs=list(specs),
                              traced=(0.0, 0.0),
                              steps=[(start + 0.1 * i, start + 0.1 * i + 0.05)
                                     for i in range(10)])
    return types.SimpleNamespace(window=w,
                                 tokens=types.SimpleNamespace(times=times),
                                 mix={"loop": mix_loop}, trace=None)


def test_readings_count_only_the_window():
    times = {"a": [9.9, 10.1, 10.3, 10.6], "b": [10.5, 10.7, 11.2]}
    run = _run(times)
    assert readings.tokens_per_s(run) == 5.0
    gaps = sorted(readings.token_gaps(run))
    assert gaps == pytest.approx([0.2, 0.2, 0.3])
    assert readings.step_ms(run) == pytest.approx(100.0)
    run.window.traced = (10.0, 10.5)                 # steps 0-4 in the span
    assert readings.step_ms(run) == pytest.approx(100.0)
    run.window.traced = (10.0, 10.25)
    assert readings.step_ms(run) == pytest.approx(250.0 / 3)
    assert readings.itl_p95_ms(run) == pytest.approx(
        np.percentile([0.2, 0.3, 0.2], 95) * 1e3)


def test_ttft_from_scheduled_arrival():
    specs = [types.SimpleNamespace(uid="a", arrival=0.2),
             types.SimpleNamespace(uid="b", arrival=0.5),
             types.SimpleNamespace(uid="c", arrival=1.5)]     # after window
    run = _run({"a": [10.45], "b": [10.9]}, specs=specs)
    assert sorted(readings.ttfts(run)) == pytest.approx([0.25, 0.4])
    assert readings.ttft_p95_ms(_run({"a": [10.45]}, specs=specs)) == math.inf
    assert readings.ttft_p95_ms(_run({}, mix_loop="closed", specs=specs)) is None


def test_step_ms_over_the_traced_span():
    run = _run({})
    run.window.traced = (10.0, 10.5)          # steps start every 0.1 s
    assert readings.step_ms(run) == pytest.approx(100.0)


def test_traced_span_ends_before_the_profiler_stops():
    """Stopping the profiler stalls the host; the span the per-layer host
    readings divide by ends before the stop, not after it."""
    from bench import harness

    class Engine:
        has_work = True

        def submit(self, request, on_token=None):
            pass

        def step(self):
            time.sleep(0.01)

    stops = []
    profile = (lambda: None,
               lambda: (time.sleep(0.3), stops.append(time.perf_counter())))
    w = harness.drive(Engine(), [], harness.Tokens(), seconds=0.4,
                      preroll_s=0.0, opens=lambda tok: True, drain_s=0.0,
                      profile=profile, profile_s=0.2)
    span = w.traced[1] - w.traced[0]
    assert 0.2 <= span < 0.25
    assert stops and stops[0] - w.traced[1] >= 0.3
