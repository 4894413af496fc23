"""Drives the serving program through one cell: set-up, the measured window,
the drain, and the records the metric readers take their numbers from.

The program is reached only through its public serving surface:
``launch.serve.build_config``/``build_engine``, ``Engine.submit(request,
on_token=...)`` and ``Engine.step()``. Token times come from this module's
own ``on_token`` callbacks; host spans are this module's own
``jax.profiler.TraceAnnotation``s around ``submit``, ``step`` and the idle
waits between arrivals.
"""
from __future__ import annotations

import dataclasses
import logging
import time

import numpy as np

from .traffic import Spec

__all__ = ["CompileClock", "DispatchNotes", "Tokens", "Window", "drive",
           "warm"]

SPAN_STEP = "bench.step"
SPAN_SUBMIT = "bench.submit"
SPAN_WAIT = "bench.wait"


class CompileClock(logging.Handler):
    """Seconds and count of XLA backend compiles, from JAX's monitoring
    events, and the name of each program JAX compiles or loads from its
    persistent cache (its ``jax_log_compiles`` notes, kept here instead of
    printed). One per process; readers take differences."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        super().__init__(logging.WARNING)
        self.seconds = 0.0
        self.count = 0
        self.events: list[tuple[float, float]] = []   # (host time, seconds)
        self.names: list[tuple[float, str]] = []      # (host time, program)
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        jax.config.update("jax_log_compiles", True)
        for name, handler in (("jax._src.interpreters.pxla", self),
                              ("jax._src.dispatch", logging.NullHandler())):
            log = logging.getLogger(name)
            log.propagate = False
            log.addHandler(handler)

    def emit(self, record: logging.LogRecord) -> None:
        msg = record.getMessage()
        if msg.startswith("Compiling "):
            self.names.append((time.perf_counter(),
                               msg[len("Compiling "):].split(" with ")[0]))

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1
            self.events.append((time.perf_counter(), duration))

    def reading(self) -> tuple[float, int]:
        return self.seconds, self.count

    def between(self, t0: float, t1: float) -> tuple[int, float, list]:
        """(count, seconds) of the compile events that ended in ``[t0, t1)``
        and the names of the programs compiled or loaded then."""
        inside = [d for t, d in self.events if t0 <= t < t1]
        return (len(inside), sum(inside),
                [n for t, n in self.names if t0 <= t < t1])


class DispatchNotes(logging.Handler):
    """Collects the program's trace-time ``repro.dispatch`` notes: which
    path each attention site and which impl each SC-GEMM shape took."""

    def __init__(self):
        super().__init__(logging.INFO)
        self.notes: dict[str, str] = {}

    def emit(self, record: logging.LogRecord) -> None:
        site, path = record.args
        self.notes[site] = path

    def attach(self) -> "DispatchNotes":
        log = logging.getLogger("repro.dispatch")
        log.setLevel(logging.INFO)
        log.propagate = False
        log.addHandler(self)
        return self


class Tokens:
    """Per-request token streams with the host time each token arrived.

    A preempted request replays its stream from index 0; a replayed index
    is not a new token and is dropped, so each stream holds each token once
    at the time it was first emitted."""

    def __init__(self, clock=time.perf_counter):
        self.clock = clock
        self.times: dict[str, list[float]] = {}
        self.ids: dict[str, list[int]] = {}
        self.replays = 0

    def callback(self, uid, index, token, reason) -> None:
        times = self.times.setdefault(uid, [])
        if index < len(times):
            self.replays += 1
            return
        times.append(self.clock())
        self.ids.setdefault(uid, []).append(int(np.asarray(token)))


@dataclasses.dataclass
class Window:
    """What one run measured: its bounds on the host clock, the steps
    driven inside it, and the requests it sent."""
    start: float
    end: float
    specs: list
    submitted: dict            # uid -> host time it was submitted
    refused: dict              # uid -> reason
    steps: list                # (start, end) of each Engine.step in window
    stopped: float = 0.0       # end of the drain
    traced: tuple = (0.0, 0.0)  # host span of the device trace

    @property
    def seconds(self) -> float:
        return self.end - self.start


def _request(spec: Spec):
    from repro.serving import Request
    return Request(uid=spec.uid, prompt=spec.prompt, max_new_tokens=spec.max_new,
                   temperature=0.0)


def warm(engine, specs: list[Spec], tokens: Tokens) -> int:
    """Serve ``specs`` to completion (set-up: every prefill bucket, decode
    step and admission shape the cell will use); returns the steps taken."""
    for s in specs:
        engine.submit(_request(s), on_token=tokens.callback)
    n = 0
    while engine.step():
        n += 1
    return n


def drive(engine, specs: list[Spec], tokens: Tokens, *, seconds: float,
          preroll_s: float, opens, drain_s: float, profile=None,
          profile_s: float = 0.0, open_by: float = 0.0) -> Window:
    """Run the cell's traffic and measure ``seconds`` of it.

    Arrival 0 of the schedule is ``preroll_s`` after this call; a request
    is submitted once it is due (``spec.arrival`` seconds after arrival 0).
    The window opens at arrival 0 when ``opens`` is None (an open loop),
    and otherwise at the first moment after it that ``opens(tokens)``
    holds, checked between steps (a closed queue: once every slot is
    live), or ``open_by`` seconds after arrival 0, whichever comes first.
    It closes ``seconds`` later. After it the schedule runs on until every
    request due inside the window has its first token, for at most
    ``drain_s``. ``profile`` is a pair of callables, started when the
    window opens and stopped ``profile_s`` seconds later (the device
    trace)."""
    import jax

    clock = tokens.clock
    order = sorted(specs, key=lambda s: s.arrival)
    submitted: dict[str, float] = {}
    refused: dict[str, str] = {}
    steps: list[tuple[float, float]] = []
    origin = clock() + preroll_s
    start = end = None
    traced = [0.0, 0.0]
    i = 0

    def first_tokens_pending() -> bool:
        return any(0 <= s.arrival < seconds and s.uid not in refused
                   and not tokens.times.get(s.uid) for s in order)

    while True:
        now = clock()
        if start is None and now >= origin and (
                opens is None or opens(tokens) or now >= origin + open_by):
            start = origin if opens is None else now
            end = start + seconds
            if profile:
                profile[0]()
                traced[0] = clock()
        if profile and start is not None and now >= start + profile_s:
            traced[1] = clock()     # before the stop, which stalls the host
            profile[1]()
            profile = None
        while i < len(order) and origin + order[i].arrival <= now:
            s = order[i]
            with jax.profiler.TraceAnnotation(SPAN_SUBMIT):
                try:
                    engine.submit(_request(s), on_token=tokens.callback)
                    submitted[s.uid] = clock()
                except Exception as e:     # a refusal is a failed request
                    refused[s.uid] = f"{type(e).__name__}: {e}"
            i += 1
        if end is not None and now >= end:
            if now >= end + drain_s or not first_tokens_pending():
                break
        if engine.has_work:
            t = clock()
            with jax.profiler.TraceAnnotation(SPAN_STEP):
                engine.step()
            if start is not None and start <= t < end:
                steps.append((t, clock()))
            continue
        if i >= len(order) and start is None:
            raise RuntimeError("the traffic drained before the window opened")
        wake = origin + order[i].arrival if i < len(order) else now + 0.05
        with jax.profiler.TraceAnnotation(SPAN_WAIT):
            time.sleep(min(max(wake - clock(), 0.0), 0.05))
    if profile and start is not None:
        traced[1] = clock()
        profile[1]()
    return Window(start=start, end=end, specs=specs, submitted=submitted,
                  refused=refused, steps=steps, stopped=clock(),
                  traced=tuple(traced))
