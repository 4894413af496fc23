"""Reduction of a profiler trace of the window to the numbers the per-layer
metrics read: device busy time as the union of the intervals in which an
operation ran, SC-GEMM device time, how many times each step program ran,
and the ``breakdown`` (the device operations that took most time, by
program and instruction, and the longest idle gaps labelled by the host
span the harness was in).

The trace is the ``.xplane.pb`` the JAX profiler writes; it is read with
``jax.profiler.ProfileData`` and nothing else.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

__all__ = ["TraceSummary", "reduce", "reduce_file", "union_seconds"]

#: Host spans of the harness (``bench.harness``), by which idle gaps are
#: labelled; a gap in none of them is "host".
HOST_SPANS = ("bench.step", "bench.submit", "bench.wait")

#: The SC-GEMM's Pallas kernel, by the instruction name its custom call gets
#: from the kernel function. The trace carries no name stack, so the XLA ops
#: that quantise and lay out the kernel's operands cannot be told apart from
#: the rest of the step; they are left out of the SC-GEMM's device time.
SC_KERNEL = "sc_matmul_counts_pallas"

#: Operations that contain others (a scanned layer loop): counted in the
#: busy union, left out of the per-operation breakdown.
CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass
class Op:
    name: str           # the HLO instruction name, without its number
    start: int          # ns on the trace's clock
    end: int
    module: str = ""    # the program it ran in


@dataclasses.dataclass
class TraceSummary:
    window_s: float                 # traced window (first to last host span)
    busy_s: float                   # union of op intervals, mean over chips
    sc_seconds: float               # device time of the SC-GEMM kernel
    modules: dict                   # program name -> executions in window
    sc_calls: list                  # (M, K, N, count) of SC-GEMM calls
    top_ops: list                   # [name, seconds]
    gaps: list                      # [host span, seconds]
    host_start: float = 0.0         # the window on the harness's clock
    host_end: float = 0.0

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops[:10], "idle_gaps": self.gaps[:10]}


def union_seconds(intervals) -> tuple[float, list]:
    """Length of the union of ``(start, end)`` ns intervals, in seconds, and
    the merged intervals in order."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged) * 1e-9, merged


def instruction(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion``: an operation's
    name without its number, stable across programs and runs."""
    head = event_name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"(\.\d+)+$", "", head) or head


def reduce_file(path: str) -> dict:
    """Raw reduction of one ``.xplane.pb``: device operations per chip (each
    tagged with the program it ran in), program executions, and the
    harness's host spans."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    chips: dict[str, list[Op]] = {}
    modules: dict[str, int] = {}
    spans: list[tuple[int, int, str]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and "NON_CORE" not in plane.name:
            ops = chips.setdefault(plane.name, [])
            runs = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend(Op(name=instruction(ev.name),
                                  start=int(ev.start_ns),
                                  end=int(ev.start_ns + ev.duration_ns))
                               for ev in line.events)
                elif line.name == "XLA Modules":
                    for ev in line.events:
                        name = re.sub(r"\(\d+\)$", "", ev.name)
                        modules[name] = modules.get(name, 0) + 1
                        runs.append((int(ev.start_ns),
                                     int(ev.start_ns + ev.duration_ns), name))
            _tag_modules(ops, sorted(runs))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in HOST_SPANS:
                        spans.append((int(ev.start_ns),
                                      int(ev.start_ns + ev.duration_ns),
                                      ev.name))
    return {"chips": chips, "modules": modules, "spans": sorted(spans)}


def _tag_modules(ops: list, runs: list) -> None:
    """Set each operation's ``module`` to the program execution whose
    interval holds its start."""
    ops.sort(key=lambda o: o.start)
    i = 0
    for o in ops:
        while i < len(runs) and runs[i][1] < o.start:
            i += 1
        if i < len(runs) and runs[i][0] <= o.start:
            o.module = runs[i][2]


def summarize(raw: dict, *, sc_calls_per_module=None) -> TraceSummary:
    """Busy, SC-GEMM time and breakdown from :func:`reduce_file`'s output.
    ``sc_calls_per_module`` maps a program name to the SC-GEMM calls
    ``(M, K, N, count)`` one execution of it makes."""
    spans = raw["spans"]
    if not spans or not raw["chips"]:
        return TraceSummary(0.0, 0.0, 0.0, {}, [], [], [])
    t0 = spans[0][0]
    t1 = max(e for _, e, _ in spans)
    window_s = (t1 - t0) * 1e-9
    busy, sc, per_cat = [], 0.0, {}
    merged0 = []
    for i, ops in enumerate(raw["chips"].values()):
        inside = [o for o in ops if o.end > t0 and o.start < t1]
        b, merged = union_seconds((max(o.start, t0), min(o.end, t1))
                                  for o in inside)
        busy.append(b)
        if i == 0:
            merged0 = merged
            for o in inside:
                if o.name in CONTAINERS:
                    continue
                d = (min(o.end, t1) - max(o.start, t0)) * 1e-9
                cat = f"{o.module}/{o.name}" if o.module else o.name
                per_cat[cat] = per_cat.get(cat, 0.0) + d
                if o.name == SC_KERNEL:
                    sc += d
    gaps = []
    prev = t0
    for s, e in merged0 + [[t1, t1]]:
        if s > prev:
            mid = (prev + s) // 2
            label = next((n for a, b, n in spans if a <= mid < b), "host")
            gaps.append([label, (s - prev) * 1e-9])
        prev = max(prev, e)
    gaps.sort(key=lambda g: -g[1])
    top = sorted(([k, v] for k, v in per_cat.items()), key=lambda x: -x[1])
    calls = []
    for mod, n in raw["modules"].items():
        for m, k, nn, c in (sc_calls_per_module or {}).get(mod, ()):
            calls.append((m, k, nn, c * n))
    return TraceSummary(window_s=window_s, busy_s=sum(busy) / len(busy),
                        sc_seconds=sc, modules=dict(raw["modules"]),
                        sc_calls=calls, top_ops=top, gaps=gaps)


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(files) != 1:
        raise RuntimeError(f"expected one trace file under {trace_dir}, "
                           f"found {files}")
    return files[0]


def reduce(trace_dir: str, host_span, sc_calls_per_module=None
           ) -> TraceSummary:
    """The summary of the one trace under ``trace_dir``; ``host_span`` is
    the traced window on the harness's clock."""
    summary = summarize(reduce_file(find_xplane(trace_dir)),
                        sc_calls_per_module=sc_calls_per_module)
    summary.host_start, summary.host_end = host_span
    return summary
