"""The engine's spans and counters (DESIGN.md §10): a small paged, chunked,
prefix-cached engine traced on the CPU, the counter surface against
``run()``'s stats, and the readings ``bench/engine_trace.py`` takes from
both, on synthetic records."""
import glob
import os
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import engine_trace as et
from bench import tracing
from repro.configs.base import ModelConfig
from repro.models import bind
from repro.serving import Engine, Request

CFG = ModelConfig(name="trace-dense", family="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
                  vocab_size=128, dtype="float32", q_block=16, kv_block=16,
                  loss_chunk=16, remat=False).validate()

#: Every span a plain (not speculative) engine with a prefix cache opens.
SPANS = {"engine.step", "engine.prefill", "engine.prefix_match",
         "engine.prefill_chunk", "engine.admit", "engine.grow_pages",
         "engine.decode", "engine.sync", "engine.sample", "engine.evict"}
PER_REQUEST = {"engine.prefix_match", "engine.prefill_chunk", "engine.admit",
               "engine.evict"}


@pytest.fixture(scope="module")
def params():
    return bind(CFG).init_params(jax.random.PRNGKey(0))


def _prompt(n, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(0, CFG.vocab_size, size=(n,)).astype(np.int32)


def _engine(params, **kw):
    return Engine(CFG, params, **{"capacity": 2, "max_seq": 32, "block": 4,
                                  "chunk": 4, **kw})


def _shared_pair():
    """Two 16-token prompts, the second a copy of the first: with pages of 4
    and chunks of 4 the second resumes at 12 (the last page is computed
    again, for its logits), so 12 of the 32 prompt tokens are saved."""
    p = _prompt(16, seed=7)
    return [Request(uid="a", prompt=p, max_new_tokens=3),
            Request(uid="b", prompt=p.copy(), max_new_tokens=3)]


@pytest.fixture(scope="module")
def traced(params, tmp_path_factory):
    engine = _engine(params)
    engine.run(_shared_pair()[:1])                   # compile outside
    requests = [Request(uid=f"t{i}", prompt=_prompt(9 + 3 * i, seed=i),
                        max_new_tokens=4) for i in range(3)]
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(trace_dir):
        engine.run(requests)
    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return et.spans(path), [r.uid for r in requests]


def test_trace_holds_every_engine_span(traced):
    spans, _ = traced
    assert {s.name for s in spans} == SPANS
    assert all(s.end >= s.start for s in spans)


def test_engine_spans_nest_inside_a_step(traced):
    spans, _ = traced
    steps = [s for s in spans if s.name == "engine.step"]
    assert steps
    for s in spans:
        if s.name != "engine.step":
            assert any(t.start <= s.start and s.end <= t.end for t in steps), s
    syncs = [s for s in spans if s.name == "engine.sync"]
    # one sync per decode step, one per final prefill chunk
    assert len(syncs) == (sum(s.name == "engine.decode" for s in spans)
                          + sum(s.name == "engine.admit" for s in spans))


def test_spans_of_one_request_share_its_uid(traced):
    spans, uids = traced
    assert {s.uid for s in spans if s.name in PER_REQUEST} == set(uids)
    assert all(s.uid is None for s in spans if s.name not in PER_REQUEST)
    for uid in uids:
        assert {s.name for s in spans if s.uid == uid} == PER_REQUEST


def test_host_ms_of_a_real_trace_is_under_the_step(traced):
    spans, _ = traced
    steps = [s for s in spans if s.name == "engine.step"]
    t0, t1 = steps[0].start, steps[-1].end
    mean_step_ms = sum(s.end - s.start for s in steps) / len(steps) * 1e-6
    assert 0 < et.host_ms(spans, t0, t1) < mean_step_ms


#: ``run()`` stats keys that are counter differences.
STATS_FROM_COUNTERS = ("decode_steps", "prefills", "prefill_chunks",
                       "preemptions", "nonfinite_logit_rows", "prefix_hits",
                       "prefix_misses", "prefill_tokens_saved", "cow_copies",
                       "prefix_reclaims")


@pytest.mark.parametrize("budget", [None, 8], ids=["roomy", "preempting"])
def test_counter_difference_over_run_is_its_stats(params, budget):
    engine = _engine(params, n_blocks=budget)
    engine.run(_shared_pair()[:1])
    requests = [Request(uid=f"r{i}", prompt=_prompt(6 + 5 * i, seed=10 + i),
                        max_new_tokens=10) for i in range(3)]
    c0 = engine.counters()
    engine.run(requests)
    c1 = engine.counters()
    assert all(isinstance(v, (int, float)) for v in c1.values())
    for k in STATS_FROM_COUNTERS:
        assert c1[k] - c0[k] == engine.stats[k], k
    assert (engine.stats["preemptions"] > 0) == (budget is not None)
    assert c1["steps"] - c0["steps"] >= engine.stats["decode_steps"]
    assert c1["queue_pops"] - c0["queue_pops"] == \
        len(requests) + engine.stats["preemptions"]


def test_decode_rows_are_the_tokens_after_the_first(params):
    engine = _engine(params)
    results = engine.run([Request(uid=f"d{i}", prompt=_prompt(5 + 4 * i, i),
                                  max_new_tokens=3 + 2 * i)
                          for i in range(4)])
    c = engine.counters()
    assert c["preemptions"] == 0
    assert c["decode_rows"] == sum(r.n_generated - 1 for r in results)
    assert c["prefill_rows"] == sum(r.prompt_len for r in results)
    assert c["prompt_tokens"] == sum(r.prompt_len for r in results)
    assert c["decode_rows"] <= c["decode_steps"] * engine.capacity


def test_queue_wait_is_counted_per_pop(params):
    engine = _engine(params)
    engine.run([Request(uid=f"q{i}", prompt=_prompt(8, i), max_new_tokens=4)
                for i in range(5)])
    c = engine.counters()
    assert c["queue_pops"] == 5
    assert c["queue_wait_s"] >= 0
    assert et.queue_wait_ms(({"queue_pops": 0, "queue_wait_s": 0.0}, c)) >= 0


def test_shared_prefix_pair_saves_the_known_share(params):
    engine = _engine(params)
    c0 = engine.counters()
    engine.run(_shared_pair())
    c1 = engine.counters()
    assert c1["prefix_hits"] - c0["prefix_hits"] == 1
    assert c1["prefill_tokens_saved"] - c0["prefill_tokens_saved"] == 12
    assert c1["prompt_tokens"] - c0["prompt_tokens"] == 32
    assert et.prefix_hit_share((c0, c1)) == pytest.approx(100 * 12 / 32)


# ------------------------------------------- readings on synthetic records

MS = 1_000_000      # ns


def _span(name, start_ms, end_ms, uid=None):
    return et.Span(name, int(start_ms * MS), int(end_ms * MS), uid)


def test_host_ms_is_the_step_less_its_syncs():
    spans = [_span("engine.step", 0, 10), _span("engine.decode", 1, 2),
             _span("engine.sync", 2, 6), _span("engine.step", 20, 30),
             _span("engine.step", 40, 50), _span("engine.sync", 41, 49)]
    # the third step starts outside the window
    assert et.host_ms(spans, 0, 40 * MS) == pytest.approx((6 + 10) / 2)
    assert et.host_ms(spans, 0, 50 * MS) == pytest.approx((6 + 10 + 2) / 3)
    assert et.host_ms(spans, 60 * MS, 70 * MS) is None
    assert et.host_ms([], 0, MS) is None


def test_counter_readings():
    c0 = {"decode_rows": 10, "decode_steps": 1, "queue_pops": 2,
          "queue_wait_s": 1.0, "prompt_tokens": 100,
          "prefill_tokens_saved": 0}
    c1 = {"decode_rows": 40, "decode_steps": 3, "queue_pops": 5,
          "queue_wait_s": 1.3, "prompt_tokens": 500,
          "prefill_tokens_saved": 300}
    assert et.decode_occupancy((c0, c1), 16) == pytest.approx(93.75)
    assert et.queue_wait_ms((c0, c1)) == pytest.approx(100.0)
    assert et.prefix_hit_share((c0, c1)) == pytest.approx(75.0)


@pytest.mark.parametrize("counters", [None, ({}, {}), ({"decode_steps": 3,
                         "decode_rows": 9, "queue_pops": 1,
                         "queue_wait_s": 0.5, "prompt_tokens": 7,
                         "prefill_tokens_saved": 0},) * 2],
                         ids=["no-snapshots", "no-counters", "no-change"])
def test_counter_readings_with_nothing_to_read(counters):
    assert et.decode_occupancy(counters, 32) is None
    assert et.queue_wait_ms(counters) is None
    assert et.prefix_hit_share(counters) is None


def _raw(ops_ms, harness):
    ops = [tracing.Op("fusion", int(a * MS), int(b * MS)) for a, b in ops_ms]
    return {"chips": {"/device:TPU:0": ops}, "modules": {},
            "spans": [(int(a * MS), int(b * MS), n) for a, b, n in harness]}


def test_idle_gaps_take_the_innermost_span():
    harness = [(0, 50, "bench.step"), (50, 60, "bench.wait"),
               (60, 100, "bench.step")]
    engine = [_span("engine.step", 1, 49), _span("engine.prefill", 2, 20),
              _span("engine.admit", 10, 20, "u1"),
              _span("engine.sync", 30, 48), _span("engine.step", 61, 99),
              _span("engine.sync", 62, 70)]
    ops = [(4, 8), (22, 28), (31, 47), (57, 58), (71, 99)]
    idle = et.idle_by_span(_raw(ops, harness), engine)
    assert idle == pytest.approx({
        "engine.admit": 14e-3,      # [8, 22): midpoint 15
        "engine.sync": 13e-3,       # [58, 71): midpoint 64.5
        "bench.wait": 10e-3,        # [47, 57): midpoint 52
        "engine.prefill": 4e-3,     # [0, 4): midpoint 2
        "engine.step": 3e-3,        # [28, 31): between its children
        "bench.step": 1e-3,         # [99, 100): after the engine's step
    })
    assert list(idle) == ["engine.admit", "engine.sync", "bench.wait",
                          "engine.prefill", "engine.step", "bench.step"]
    # every idle second of the window is labelled, once
    busy = 4 + 6 + 16 + 1 + 28
    assert sum(idle.values()) == pytest.approx((100 - busy) * 1e-3)
    # a program without engine spans: the harness's own labels
    assert et.idle_by_span(_raw(ops, harness), []) == pytest.approx(
        {"bench.step": 35e-3, "bench.wait": 10e-3})
    assert et.idle_by_span({"chips": {}, "modules": {}, "spans": []},
                           engine) is None


def test_innermost_is_host_outside_every_span():
    ordered = [_span("engine.step", 0, 10), _span("engine.sync", 2, 4)]
    starts = [s.start for s in ordered]
    assert et.innermost(ordered, starts, 3 * MS) == "engine.sync"
    assert et.innermost(ordered, starts, 5 * MS) == "engine.step"
    assert et.innermost(ordered, starts, 11 * MS) == "host"
    assert et.innermost(ordered, starts, -1) == "host"


# ------------------------------------------------ one traced benchmark run

def test_traced_run_reads_the_engine(tmp_path):
    """A whole run of the benchmark's open-loop rehearsal mix at a tiny
    width, traced, through ``engine_trace.traced_run``: its hooks see the
    engine, snapshot the counters around the trace and read the trace's
    engine spans. The CPU trace has no device plane, so no idle gap is
    labelled."""
    import time

    from bench.tests.test_rehearsal import OPEN, PEAK, spec

    out = et.traced_run(spec(OPEN), seed=5, seconds=2.0,
                        devices=jax.devices(), peak=PEAK,
                        t_process=time.perf_counter(), say=lambda m: None)
    assert out["result"]["correct"], out["result"]["compared"]
    r = out["engine"]
    assert 0 < r["engine_host_ms"] < r["step_ms"]
    assert 0 < r["decode_occupancy"] <= 100
    assert 0 < r["prefix_hit_share"] < 100
    assert r["queue_wait_ms"] >= 0
    assert r["counters"]["steps"] > 0 and r["counters"]["queue_pops"] > 0
    assert r["span_count_and_s"]["engine.step"][0] > 0
    assert r["device_idle_share"] is None and r["idle_s_by_span"] is None


# ------------------------------------------ a two-step trace from the chip

#: Two engine steps of the ``sc8-offline`` cell on one TPU v5e (32 slots
#: decoding while a 33rd prompt is prefilled), recorded by
#: ``bench/tests/record_engine_trace.py``.
CHIP_TRACE = (Path(__file__).resolve().parents[1] / "bench" / "tests" / "data"
              / "sc8_engine_two_steps.xplane.pb")


@pytest.fixture(scope="module")
def chip_trace():
    path = str(CHIP_TRACE)
    return tracing.reduce_file(path), et.spans(path)


def test_chip_trace_steps_hold_their_engine_spans(chip_trace):
    raw, spans = chip_trace
    harness = raw["spans"]
    assert [n for _, _, n in harness] == ["bench.step", "bench.step"]
    for a, b, _ in harness:
        inside = {s.name for s in spans if a <= s.start and s.end <= b}
        assert inside == {"engine.step", "engine.prefill",
                          "engine.prefill_chunk", "engine.grow_pages",
                          "engine.decode", "engine.sync", "engine.sample"}
    assert {s.uid for s in spans if s.name == "engine.prefill_chunk"} == \
        {"r32"}


def test_chip_trace_idle_gaps_are_labelled_by_engine_spans(chip_trace):
    raw, spans = chip_trace
    summary = tracing.summarize(raw)
    idle = et.idle_by_span(raw, spans)
    assert all(k.startswith("engine.") for k in idle)
    assert sum(idle.values()) == pytest.approx(
        summary.window_s - summary.busy_s, rel=1e-6)
    # the host waits on the decode's logits with the device idle
    assert max(idle, key=idle.get) == "engine.sync"
    steps = [s for s in spans if s.name == "engine.step"]
    mean_step_ms = sum(s.end - s.start for s in steps) / len(steps) * 1e-6
    assert 0 < et.host_ms(spans, *et.window(raw)) < 0.2 * mean_step_ms
