"""The trace reduction on a small trace recorded on the chip: two engine
steps of the ``sc8-offline`` cell (a 128-row prefill chunk and the batched
decode over 32 slots, smollm-360m at full width through the SC-GEMM
kernel) on one TPU v5e, with the harness's profiler settings.

    PYTHONPATH=src:. JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
from pathlib import Path

import pytest

from bench import cell, tracing
from bench.tests.test_yardstick import SMOLLM

TRACE = Path(__file__).resolve().parent / "data" / "sc8_two_steps.xplane.pb"


@pytest.fixture(scope="module")
def raw():
    return tracing.reduce_file(str(TRACE))


def test_planes_lines_and_spans(raw):
    assert list(raw["chips"]) == ["/device:TPU:0"]
    ops = raw["chips"]["/device:TPU:0"]
    assert len(ops) > 1000
    assert all(o.end >= o.start for o in ops)
    assert [n for _, _, n in raw["spans"]] == ["bench.step", "bench.step"]
    # one chunked-prefill program and one decode program per step
    assert raw["modules"]["jit_step"] == 2
    assert raw["modules"]["jit_decode"] == 2
    assert {o.module for o in ops if o.name == tracing.SC_KERNEL} == \
        {"jit_step", "jit_decode"}


def test_busy_is_a_union_inside_the_window(raw):
    s = tracing.summarize(raw, sc_calls_per_module=cell.sc_calls_per_module(
        SMOLLM, 32, 128))
    ops = raw["chips"]["/device:TPU:0"]
    total = sum(o.end - o.start for o in ops) * 1e-9
    assert 0 < s.busy_s <= s.window_s
    assert s.busy_s <= total + 1e-9
    # the SC-GEMM is most of the device time of an SC step
    assert 0.5 * s.busy_s < s.sc_seconds <= total
    assert s.sc_calls and all(c[3] > 0 for c in s.sc_calls)
    bd = s.breakdown()
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert all(sec > 0 for _, sec in bd["device_ops"] + bd["idle_gaps"])
    assert sum(sec for _, sec in bd["idle_gaps"]) <= s.window_s - s.busy_s + 1e-6


def test_sc_roofline_share_is_a_share(raw):
    from bench import readings, work
    s = tracing.summarize(raw, sc_calls_per_module=cell.sc_calls_per_module(
        SMOLLM, 32, 128))
    run = type("Run", (), {"trace": s, "peak": work.peaks("TPU v5 lite"),
                           "config_file": {"numerics": {"sc_bits": 8}}})()
    share = readings.sc_gemm_roofline(run)
    assert 0 < share < 100
