"""Numbers a run yields, computed from the harness's own records (host
clock) and the reduced device trace. Each metric's file under
``bench/metrics/`` reads one of them; a reading with nothing to read is
``None`` and the metric is left out of the result line."""
from __future__ import annotations

import math

import numpy as np

from . import work

__all__ = ["window_tokens", "token_gaps", "ttfts", "percentile",
           "tokens_per_s", "itl_p95_ms", "ttft_p95_ms", "step_ms",
           "device_idle_share", "model_flops", "mfu", "sc_gemm_roofline"]


def percentile(values, q: float) -> float:
    """``q``-th percentile by linear interpolation between order statistics
    (numpy's default); an unreachable value (``inf``) counts as the
    largest."""
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        return math.nan
    pos = q / 100.0 * (v.size - 1)
    lo, hi = int(math.floor(pos)), int(math.ceil(pos))
    if math.isinf(v[hi]):
        return math.inf
    return float(v[lo] + (v[hi] - v[lo]) * (pos - lo))


def window_tokens(run) -> list[float]:
    """Host times of every generated token that arrived inside the window."""
    w = run.window
    return [t for times in run.tokens.times.values() for t in times
            if w.start <= t < w.end]


def token_gaps(run) -> list[float]:
    """Every gap between two consecutive tokens of one request, both inside
    the window."""
    w = run.window
    gaps = []
    for times in run.tokens.times.values():
        inside = [t for t in times if w.start <= t < w.end]
        gaps.extend(np.diff(inside).tolist())
    return gaps


def ttfts(run) -> list[float]:
    """Seconds from each request's scheduled arrival to its first token, for
    every request due inside the window; one that never got a first token
    (refused, or still waiting when the drain ended) reads ``inf``."""
    w = run.window
    out = []
    for s in run.window.specs:
        if not 0 <= s.arrival < w.seconds:
            continue
        times = run.tokens.times.get(s.uid)
        out.append(times[0] - (w.start + s.arrival) if times else math.inf)
    return out


def tokens_per_s(run) -> float:
    return len(window_tokens(run)) / run.window.seconds


def itl_p95_ms(run) -> float | None:
    gaps = token_gaps(run)
    return percentile(gaps, 95) * 1e3 if gaps else None


def ttft_p95_ms(run) -> float | None:
    if run.mix["loop"] != "open":
        return None           # a closed queue has no arrival to count from
    t = ttfts(run)
    return percentile(t, 95) * 1e3 if t else None


def step_ms(run) -> float | None:
    """Milliseconds per ``Engine.step`` on the host clock: the traced span
    (from the start of the trace to the moment before it is stopped) over
    the steps started in it, or the window over its steps in an untraced
    run."""
    t0, t1 = run.window.traced
    if t1 <= t0:
        t0, t1 = run.window.start, run.window.end
    n = sum(1 for s, _ in run.window.steps if t0 <= s < t1)
    return (t1 - t0) / n * 1e3 if n else None


def device_idle_share(run) -> float | None:
    """Percent of the traced window in which no operation ran on the
    device."""
    tr = run.trace
    if tr is None or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)


def model_flops(run, t0: float, t1: float) -> int:
    """Model FLOPs of the token rows the forward computed for tokens that
    arrived in ``[t0, t1)``: each decode token is one row (its logits
    sampled); each first token stands for its whole prompt's rows (the last
    one sampled). Padding is not counted. A mix whose prompts share
    prefixes has rows the prefix cache served, which this does not know,
    so it refuses such a mix."""
    if run.mix.get("system_prompts"):
        raise ValueError("model_flops counts whole prompts; this mix shares "
                         "prefixes")
    s = run.sizes
    total = 0
    for uid, times in run.tokens.times.items():
        p = len(run.spec_by_uid[uid].prompt)
        for j, t in enumerate(times):
            if t0 <= t < t1:
                total += (work.request_flops(s, 0, p, 1) if j == 0 else
                          work.request_flops(s, p + j - 1, p + j, 1))
    return total


def mfu(run) -> float | None:
    """Percent of the chip's bf16 peak that the model's FLOPs in the traced
    window would take."""
    tr = run.trace
    if tr is None or tr.window_s <= 0:
        return None
    flops = model_flops(run, tr.host_start, tr.host_end)
    if not flops:
        return None
    return 100.0 * flops / (tr.window_s * run.peak["bf16_flops_per_s"])


def sc_gemm_roofline(run) -> float | None:
    """Percent: least time of the traced window's SC-GEMM calls at their
    logical shapes (the larger of operations at the int8 peak and bytes at
    the HBM peak) over the device time the trace gives those calls."""
    tr = run.trace
    if tr is None or not tr.sc_seconds or not tr.sc_calls:
        return None
    bits = run.config_file["numerics"]["sc_bits"]
    least = work.sc_least_seconds(tr.sc_calls, bits, run.peak)
    return 100.0 * least / tr.sc_seconds
