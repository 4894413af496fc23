"""The one traffic generator: reads a mix's parameters from
``bench/traffic/<mix>.json`` and turns them and a seed into requests.

A mix fixes the *shape* of its traffic (prompt and output lengths in their
order, the shared system prompt each request names, the arrival schedule)
from its own ``shape_seed``; lengths are whole tokens, not rounded to
pages. The run seed draws every token id (and the weights). So every seed
offers the window the same work: a seed that drew its own lengths, or
reordered them, would change the tokens offered to a 51 s window of the
chat mix by 13% or 6% (quartile spread over 40 seeds), and that would be
the bound of every metric. The set of prompt lengths the program sees is
known before the run, so set-up can warm each one.

Two loops:

* ``closed``: a queue of ``requests`` submitted before the window opens; the
  system drains it as fast as it can.
* ``open``: arrivals on a fixed schedule, starting ``preroll_s`` before the
  window and running ``tail_s`` past it, at ``rate_per_s`` on average. The
  gaps between arrivals follow ``arrivals.process``: ``poisson``
  (exponential gaps) or ``gamma`` (gamma gaps with coefficient of variation
  ``cv``; ``cv`` 1 is Poisson, above 1 bursty). The schedule never waits on
  a completion.
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path

import numpy as np

__all__ = ["Spec", "load_mix", "lengths", "gaps", "generate"]

HERE = Path(__file__).resolve().parent


@dataclasses.dataclass
class Spec:
    """One request as the benchmark sends it: due ``arrival`` seconds after
    the window opens (negative in the pre-roll)."""
    uid: str
    arrival: float
    prompt: np.ndarray
    max_new: int
    system: int = -1            # index of its shared system prompt, or -1


def load_mix(name: str) -> dict:
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def lengths(dist: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` whole-token lengths from a length distribution, clipped to
    ``[low, high]``."""
    low, high = int(dist["low"]), int(dist["high"])
    if dist["dist"] == "uniform":
        x = rng.uniform(low, high + 1, n)
    elif dist["dist"] == "lognormal":
        x = np.exp(rng.normal(math.log(dist["median"]), dist["sigma"], n))
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.floor(x), low, high).astype(np.int64)


def gaps(arrivals: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` gaps between arrivals, in seconds, with mean
    ``1 / rate_per_s``."""
    mean = 1.0 / float(arrivals["rate_per_s"])
    process = arrivals["process"]
    if process == "poisson":
        return rng.exponential(mean, n)
    if process == "gamma":
        shape = float(arrivals["cv"]) ** -2
        return rng.gamma(shape, mean / shape, n)
    raise ValueError(f"unknown arrival process {process!r}")


def _count(mix: dict, seconds: float) -> int:
    if mix["loop"] == "closed":
        return int(mix["requests"])
    arr = mix["arrivals"]
    span = arr["preroll_s"] + seconds + arr["tail_s"]
    return int(math.ceil(arr["rate_per_s"] * span))


def _shape(mix: dict, seconds: float) -> dict:
    """The seed-independent request sizes, system prompts and arrival
    times, in arrival order."""
    rng = np.random.default_rng(int(mix["shape_seed"]))
    n = _count(mix, seconds)
    shape = {"user": lengths(mix["prompt"], n, rng),
             "out": lengths(mix["output"], n, rng),
             "system": np.full(n, -1), "arrival": np.zeros(n)}
    sp = mix.get("system_prompts")
    if sp:
        w = 1.0 / np.arange(1, sp["count"] + 1) ** sp["zipf_s"]
        shape["system"] = rng.choice(sp["count"], n, p=w / w.sum())
    if mix["loop"] == "open":
        t = np.cumsum(gaps(mix["arrivals"], n, rng))
        shape["arrival"] = t - t[0] - mix["arrivals"]["preroll_s"]
    return shape


def generate(mix: dict, seed: int, seconds: float, vocab: int):
    """The run's requests in arrival order, its warm-up requests, and the
    longer warm-up of a checkout's first run.

    Warm-up serves, before the window, every prompt length the run can send
    through the admission path the run will take: with system prompts, one
    request per system prompt first (which also leaves each in the prefix
    cache, as on a server that has been up), then one request of one token
    per distinct length behind a system prompt; without them, one per
    distinct length with a prompt of its own (the first of them decodes
    once). So every prefill bucket, the decode step and
    every admission shape runs once. A checkout's first run serves the
    whole multiset of the mix once before that, as a closed queue, so that
    the programs the run's evictions and page reclaims compile are in the
    persistent compilation cache for every later run."""
    shape = _shape(mix, seconds)
    rng = np.random.default_rng(np.random.SeedSequence(int(seed)))
    n = len(shape["user"])
    sp = mix.get("system_prompts")
    systems = ([rng.integers(0, vocab, sp["tokens"], dtype=np.int32)
                for _ in range(sp["count"])] if sp else [])

    def tokens(k):
        return rng.integers(0, vocab, int(k), dtype=np.int32)

    def prompt(s, user):
        return np.concatenate([systems[s], tokens(user)]) if s >= 0 \
            else tokens(user)

    specs = [Spec(uid=f"r{i}", arrival=float(shape["arrival"][i]),
                  prompt=prompt(int(shape["system"][i]), shape["user"][i]),
                  max_new=int(shape["out"][i]), system=int(shape["system"][i]))
             for i in range(n)]
    users = sorted({int(u) for u in shape["user"]})
    warm = [Spec(uid=f"ws{k}", arrival=0.0, system=k, max_new=2,
                 prompt=prompt(k, users[0]))
            for k in range(len(systems))]
    for k, u in enumerate(users):
        s = k % len(systems) if systems else -1
        warm.append(Spec(uid=f"w{k}", arrival=0.0,
                         max_new=1 if systems or k else 2,
                         prompt=prompt(s, u), system=s))
    first = [Spec(uid=f"wx{i}", arrival=0.0, max_new=int(shape["out"][i]),
                  prompt=prompt(int(shape["system"][i]), shape["user"][i]),
                  system=int(shape["system"][i]))
             for i in range(n)]
    return specs, warm, first
