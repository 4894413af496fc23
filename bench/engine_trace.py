"""The serving engine's own spans and counters, read for one traced run.

The engine wraps each step's work in ``engine.*`` host spans
(``jax.profiler.TraceAnnotation``: they land on the trace's host plane, on
the clock of the device's operations) and totals its work in
``Engine.counters()``. This module reads both:

- :func:`spans`: the ``engine.*`` spans of an ``.xplane.pb``, each with the
  request ``uid`` it carries;
- :func:`idle_by_span`: the device-idle seconds of the traced window (the
  window and the busy union exactly as :mod:`bench.tracing` computes them),
  each idle gap labelled by the innermost span, engine or harness, that
  holds its midpoint;
- :func:`host_ms`: the host's own milliseconds per ``engine.step`` (the
  step's span less its ``engine.sync`` children, where the host waits on
  the device);
- :func:`decode_occupancy`, :func:`queue_wait_ms`, :func:`prefix_hit_share`:
  readings of two counter snapshots.

Each returns ``None`` where it has nothing to read (a program without the
spans or the counters).

    python3 bench/engine_trace.py --workload <name> --seed <n> --seconds <s>

runs the cell once, traced, as ``bench/run.py --trace 1`` does, snapshots
the engine's counters when the trace starts and just before it stops, and
prints one JSON line last: the run's result and these readings.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import bisect  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]

__all__ = ["Span", "spans", "window", "innermost", "idle_by_span", "host_ms",
           "decode_occupancy", "queue_wait_ms", "prefix_hit_share",
           "traced_run"]

PREFIX = "engine."


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: int          # ns on the trace's clock
    end: int
    uid: str | None = None


def spans(path: str) -> list[Span]:
    """The ``engine.*`` host spans of one ``.xplane.pb``, by start (an
    enclosing span before the spans it holds)."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    start = int(ev.start_ns)
                    out.append(Span(ev.name, start,
                                    start + int(ev.duration_ns),
                                    dict(ev.stats).get("uid")))
    return sorted(out, key=lambda s: (s.start, -s.end))


def window(raw: dict) -> tuple[int, int] | None:
    """The traced window on the trace's clock: first to last harness span
    of :func:`bench.tracing.reduce_file`'s output."""
    if not raw["spans"]:
        return None
    return raw["spans"][0][0], max(e for _, e, _ in raw["spans"])


def innermost(ordered: list, starts: list, t: int) -> str:
    """Name of the innermost span holding ``t``, of spans that nest and are
    ordered as :func:`spans` orders them (``starts`` their starts); "host"
    where none does. The latest-started span that has not ended by ``t``
    is the innermost: every span started after it ended before ``t``."""
    i = bisect.bisect_right(starts, t)
    while i > 0:
        i -= 1
        if ordered[i].end > t:
            return ordered[i].name
    return "host"


def idle_by_span(raw: dict, engine_spans: list) -> dict | None:
    """Device-idle seconds inside the traced window, summed by the innermost
    span holding each idle gap's midpoint (first chip), largest first."""
    from bench import tracing

    w = window(raw)
    if w is None or not raw["chips"]:
        return None
    t0, t1 = w
    ops = next(iter(raw["chips"].values()))
    _, merged = tracing.union_seconds(
        (max(o.start, t0), min(o.end, t1)) for o in ops
        if o.end > t0 and o.start < t1)
    ordered = sorted([Span(n, s, e) for s, e, n in raw["spans"]]
                     + list(engine_spans), key=lambda s: (s.start, -s.end))
    starts = [s.start for s in ordered]
    idle: dict[str, float] = {}
    prev = t0
    for s, e in merged + [[t1, t1]]:
        if s > prev:
            label = innermost(ordered, starts, (prev + s) // 2)
            idle[label] = idle.get(label, 0.0) + (s - prev) * 1e-9
        prev = max(prev, e)
    return dict(sorted(idle.items(), key=lambda kv: -kv[1]))


def host_ms(engine_spans: list, t0: int, t1: int) -> float | None:
    """Mean over the ``engine.step`` spans started in ``[t0, t1)`` of the
    span's length less its ``engine.sync`` children, in milliseconds."""
    steps = [s for s in engine_spans
             if s.name == "engine.step" and t0 <= s.start < t1]
    if not steps:
        return None
    syncs = [s for s in engine_spans if s.name == "engine.sync"]
    starts = [s.start for s in syncs]
    total = 0
    for st in steps:
        i = bisect.bisect_left(starts, st.start)
        waited = 0
        while i < len(syncs) and syncs[i].start < st.end:
            waited += syncs[i].end - syncs[i].start
            i += 1
        total += st.end - st.start - waited
    return total / len(steps) * 1e-6


def _delta(counters, key: str):
    if not counters or key not in counters[0]:
        return None
    c0, c1 = counters
    return c1[key] - c0[key]


def decode_occupancy(counters, capacity: int) -> float | None:
    """Percent of the decode rows computed that were live slots: decode
    rows over decode steps times the capacity, between two snapshots."""
    rows = _delta(counters, "decode_rows")
    steps = _delta(counters, "decode_steps")
    return 100.0 * rows / (steps * capacity) if steps else None


def queue_wait_ms(counters) -> float | None:
    """Mean milliseconds a request popped between two snapshots waited in
    the queue since its submit or requeue."""
    pops = _delta(counters, "queue_pops")
    return 1e3 * _delta(counters, "queue_wait_s") / pops if pops else None


def prefix_hit_share(counters) -> float | None:
    """Percent of the prompt tokens admitted between two snapshots that a
    prefix hit seeded instead of computing."""
    prompt = _delta(counters, "prompt_tokens")
    if not prompt:
        return None
    return 100.0 * _delta(counters, "prefill_tokens_saved") / prompt


# ------------------------------------------------------------------- run

def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    return ap.parse_args(argv)


def readings(found: dict, record, engine) -> dict:
    """The engine readings of one traced run: ``found`` holds the counter
    snapshots, the raw trace and its engine spans."""
    from bench import readings as bench_readings

    counters = (found.get("c0"), found.get("c1"))
    if counters[0] is None:
        counters = None
    out = {"step_ms": bench_readings.step_ms(record),
           "device_idle_share": bench_readings.device_idle_share(record),
           "decode_occupancy": decode_occupancy(counters, engine.capacity),
           "queue_wait_ms": queue_wait_ms(counters),
           "prefix_hit_share": prefix_hit_share(counters),
           "counters": None if counters is None else
           {k: counters[1][k] - counters[0][k] for k in counters[0]}}
    raw, eng = found.get("raw"), found.get("spans", [])
    w = raw and window(raw)
    if w:
        idle = idle_by_span(raw, eng)
        total = sum(idle.values()) if idle else 0.0
        out["engine_host_ms"] = host_ms(eng, *w)
        out["idle_s_by_span"] = idle
        out["idle_share_in_engine_or_wait"] = (
            100.0 * sum(v for k, v in idle.items()
                        if k.startswith(PREFIX) or k == "bench.wait") / total
            if total else None)
        inside = [s for s in eng if w[0] <= s.start < w[1]]
        per: dict[str, list] = {}
        for s in inside:
            n = per.setdefault(s.name, [0, 0.0])
            n[0] += 1
            n[1] += (s.end - s.start) * 1e-9
        out["span_count_and_s"] = dict(sorted(per.items(),
                                              key=lambda kv: -kv[1][1]))
    return out


def traced_run(spec, *, seed: int, seconds: float, devices, peak: dict,
               t_process: float, warm_marker=None, say=print) -> dict:
    """One traced run of ``spec`` through ``bench.cell.execute``, with three
    hooks: the engine ``launch.serve.build_engine`` builds is kept, the
    profiler's start and stop also snapshot its counters, and the trace
    file is also read for its engine spans before the run removes it.
    Returns the run's result and the engine readings."""
    from unittest import mock

    from bench import cell as cellmod
    from bench import tracing
    from repro.launch import serve

    found: dict = {}
    build_engine, profiler = serve.build_engine, cellmod.profiler
    reduce_file = tracing.reduce_file

    def keep_engine(*a, **k):
        found["engine"] = build_engine(*a, **k)
        return found["engine"]

    def snapshotting(trace_dir):
        start, stop = profiler(trace_dir)

        def start_():
            start()
            found["c0"] = found["engine"].counters()

        def stop_():
            found["c1"] = found["engine"].counters()
            stop()
        return start_, stop_

    def reading_spans(path):
        found["raw"] = reduce_file(path)
        found["spans"] = spans(path)
        return found["raw"]

    records: list = []
    with mock.patch.object(serve, "build_engine", keep_engine), \
            mock.patch.object(cellmod, "profiler", snapshotting), \
            mock.patch.object(tracing, "reduce_file", reading_spans):
        result = cellmod.execute(
            spec, seed=seed, seconds=seconds, trace=True, devices=devices,
            peak=peak, t_process=t_process, warm_marker=warm_marker,
            records=records, say=say)
    return {"result": result,
            "engine": readings(found, records[0], found["engine"])}


def main(argv=None) -> int:
    args = parse(argv)
    from bench import cell as cellmod
    from bench import run as runmod
    from bench import work
    spec = cellmod.load(ROOT, args.workload)
    runmod.use_checkout_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < spec.chips:
        print(f"engine_trace: needs {spec.chips} TPU chip(s)", file=sys.stderr)
        return 2
    out = traced_run(
        spec, seed=args.seed, seconds=args.seconds,
        devices=devices[:spec.chips], peak=work.peaks(devices[0].device_kind),
        t_process=T_PROCESS,
        warm_marker=runmod.CACHE_DIR / "bench" / f"{spec.name}.warm",
        say=lambda m: print(m, flush=True))
    print(json.dumps({"workload": spec.name, "seed": args.seed, **out}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    sys.exit(main())
