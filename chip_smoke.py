"""Serve smollm-360m at its full published width on one TPU chip.

    python3 chip_smoke.py

The quickest proof that the serving path still starts on the chip. One
process, which holds the chip, drives ``repro.launch.serve``'s own config,
workload and ``Engine`` construction through three phases on one model and
one set of params made from ``PRNGKey(0)``:

  (a) exact: 16 requests, capacity 8, 512-token prompts sharing a
      256-token preamble, up to 64 new tokens, block 64, chunked prefill
      (chunk 128), prefix cache on;
  (b) the same with ``--sc-gemm``: every projection through the paper's
      multiplier, ``sc_impl="auto"``, which must resolve to the Pallas
      kernel (``pallas_tuned``);
  (c) the same as (a) with ``--speculate-k 3 --draft-bits 4``.

Each phase is served twice: a first engine whose run includes compilation
(set-up), then a fresh engine on the same compiled steps (steady). The times
it prints are single readings, not a benchmark.

``ok`` is true only if every phase drained every request (each at its
``max_new_tokens`` or at EOS, all ids in the vocabulary, no non-finite logit
row sampled), the Pallas SC-GEMM counts equal ``sc_matmul_reference``'s bit
for bit at every projection shape, and the exact engine's first-token logits
agree with an f32 ``highest``-precision prefill within :data:`REF_TOL`.
Stream agreement with the sequential ``launch.serve.generate`` baseline is
printed, not gated. The script refuses to run without a TPU; its last line
of standard output is one JSON object naming the device.
"""
from __future__ import annotations

import dataclasses
import json
import logging
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.sc_matmul import sc_matmul, sc_matmul_reference  # noqa: E402
from repro.core.sc_numerics import recover_counts  # noqa: E402
from repro.launch import setup_compile_cache  # noqa: E402
from repro.launch.serve import (build_config, build_engine,  # noqa: E402
                                build_parser, build_requests, generate)
from repro.launch.steps import (bucket_for,  # noqa: E402
                                cached_chunked_prefill_step)
from repro.models import bind  # noqa: E402

#: ``launch.serve`` flags shared by every phase.
SERVE_FLAGS = ("--arch", "smollm-360m", "--requests", "16", "--capacity", "8",
               "--prompt-len", "512", "--gen", "64", "--block", "64",
               "--chunk", "128")

#: (name, extra ``launch.serve`` flags) of each phase, in order.
PHASES = (("exact", ()),
          ("sc-gemm", ("--sc-gemm",)),
          ("speculative", ("--speculate-k", "3", "--draft-bits", "4")))

#: Tolerance on max|engine − reference| / max|reference| over request 0's
#: first-token logit row. The engine keeps weights, activations and the KV
#: cache in bf16, whose unit roundoff is 2^-8; each of 32 layers rounds its
#: residual stream and K/V, so an error of a few percent of the hidden RMS
#: reaches the unit-RMS logits, which span about ±4.5 over a 49152 vocab.
#: 0.05 of that span leaves room for the rounding and still fails a wrong
#: mask, layer or kernel, which moves logits by O(1).
REF_TOL = 0.05

#: Requests whose engine streams are compared with ``generate``.
AGREEMENT_REQUESTS = 4


class CompileClock:
    """Seconds and count of XLA backend compiles, from JAX's monitoring
    events. One listener per process; readers take differences."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1

    def reading(self) -> tuple[float, int]:
        return self.seconds, self.count


class DispatchNotes(logging.Handler):
    """Collects the trace-time ``repro.dispatch`` notes: which path each
    attention site and which impl each SC-GEMM shape took, and why."""

    def __init__(self):
        super().__init__(logging.INFO)
        self.notes: dict[str, str] = {}

    def emit(self, record: logging.LogRecord) -> None:
        site, path = record.args
        self.notes[site] = path


def projection_shapes(cfg) -> list[tuple[int, int]]:
    """Distinct (K, N) of the model's dense projections: QKV, O, MLP, head."""
    d, hd = cfg.d_model, cfg.head_dim
    shapes = [(d, cfg.n_heads * hd), (d, cfg.n_kv_heads * hd), (d, cfg.d_ff),
              (cfg.d_ff, d), (cfg.n_heads * hd, d), (d, cfg.vocab_size)]
    return list(dict.fromkeys(shapes))


def sc_gemm_exactness(cfg, cases, *, impl: str = "pallas_tuned") -> list[dict]:
    """``sc_matmul(impl)`` against ``sc_matmul_reference`` at every
    projection shape, for each ``(bits, m)`` in ``cases``: the de-scaled
    integer counts must be equal. Row-quantized, as ``sc_dense`` calls it."""
    out = []
    for bits, m in cases:
        for k, n in projection_shapes(cfg):
            ka, kb = jax.random.split(jax.random.PRNGKey(m * 131 + k + n))
            a = jax.random.normal(ka, (m, k), jnp.float32)
            b = jax.random.normal(kb, (k, n), jnp.float32) * k ** -0.5
            got = sc_matmul(a, b, bits=bits, impl=impl, row_quant=True)
            # a short K block bounds the oracle's (M, Kb, N) broadcast
            want = sc_matmul_reference(a, b, bits=bits, k_block=16,
                                       row_quant=True)
            same = np.array_equal(
                recover_counts(got, a, b, bits=bits, row_quant=True),
                recover_counts(want, a, b, bits=bits, row_quant=True))
            out.append({"bits": bits, "shape": (m, k, n), "equal": same})
    return out


def check_drained(cfg, requests, results, stats) -> list[str]:
    """Problems with a phase's output: every request finished at its
    budget or at EOS, ids in the vocabulary, no non-finite logit row."""
    problems = []
    if len(results) != len(requests):
        problems.append(f"{len(results)} results for {len(requests)} requests")
    for req, res in zip(requests, results):
        toks = np.asarray(res.tokens)
        if res.finished_reason == "length":
            if res.n_generated != req.max_new_tokens:
                problems.append(f"{res.uid}: {res.n_generated} tokens, "
                                f"budget {req.max_new_tokens}")
        elif not (res.finished_reason == "eos" and req.eos_id is not None
                  and int(toks[-1]) == req.eos_id):
            problems.append(f"{res.uid}: finished {res.finished_reason!r}")
        if toks.size and (toks.min() < 0 or toks.max() >= cfg.vocab_size):
            problems.append(f"{res.uid}: token id outside the vocabulary")
    if stats["nonfinite_logit_rows"]:
        problems.append(f"{stats['nonfinite_logit_rows']} non-finite logit "
                        f"rows sampled")
    return problems


def run_phase(flags, params, clock: CompileClock) -> dict:
    """Serve the ``launch.serve`` workload ``flags`` describe: first through
    an engine whose run compiles (set-up), then through a fresh engine on
    the same cached steps (steady). Returns the steady run's results and
    the phase's readings and problems."""
    args = build_parser().parse_args(list(flags))
    cfg = build_config(args)
    c0 = clock.reading()
    t0 = time.perf_counter()
    build_engine(cfg, params, args).run(build_requests(cfg, args))
    setup_s = time.perf_counter() - t0
    c1 = clock.reading()
    engine = build_engine(cfg, params, args)
    requests = build_requests(cfg, args)
    results = engine.run(requests)
    c2 = clock.reading()
    return {"cfg": cfg, "args": args, "engine": engine, "requests": requests,
            "results": results, "stats": engine.stats, "setup_s": setup_s,
            "compile_s": c1[0] - c0[0], "compiles": c1[1] - c0[1],
            "steady_compiles": c2[1] - c1[1],
            "problems": check_drained(cfg, requests, results, engine.stats)}


def engine_first_logits(engine, params, prompt) -> np.ndarray:
    """Request ``prompt``'s first-token logit row through the engine's own
    compiled chunked-prefill step (the same cached executable the engine
    admits with), from an empty staging cache."""
    bucket = bucket_for(len(prompt), engine.buckets)
    step, shardings, _ = cached_chunked_prefill_step(
        engine.cfg, engine.mesh, seq_len=bucket, chunk=engine.chunk)
    placed = jax.device_put(params, shardings["params"])
    cache = jax.device_put(bind(engine.cfg).init_cache(1, bucket),
                           shardings["cache"])
    for off in range(0, len(prompt), engine.chunk):
        nv = min(engine.chunk, len(prompt) - off)
        toks = np.zeros((engine.chunk,), np.int32)
        toks[:nv] = prompt[off:off + nv]
        logits, cache = step(placed, cache,
                             {"tokens": jnp.asarray(toks)[None],
                              "n_valid": jnp.asarray([nv], jnp.int32)})
    return np.asarray(jax.device_get(logits), np.float64)[0, -1]


def reference_logits(cfg, params, prompt) -> np.ndarray:
    """First-token logits of an f32 prefill of the same params at
    ``highest`` matmul precision: the plain reference."""
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    p32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        logits, _ = jax.jit(bind(cfg32).prefill_step)(
            p32, {"tokens": jnp.asarray(prompt)[None]})
    return np.asarray(jax.device_get(logits), np.float64)[0, -1]


def reference_error(phase: dict, params) -> float:
    """max|engine − reference| / max|reference| over request 0's
    first-token logits."""
    prompt = phase["requests"][0].prompt
    got = engine_first_logits(phase["engine"], params, prompt)
    want = reference_logits(phase["cfg"], params, prompt)
    return float(np.abs(got - want).max() / np.abs(want).max())


def generate_agreement(phase: dict, params,
                       n: int = AGREEMENT_REQUESTS) -> tuple[int, float]:
    """Engine streams against the sequential ``generate`` baseline for the
    first ``n`` requests: (streams identical, share of tokens that agree
    before the first divergence). ``generate`` runs at the workload's
    largest budget so all ``n`` share one compiled pair of steps."""
    gen = phase["args"].gen
    same = agreed = total = 0
    for req, res in list(zip(phase["requests"], phase["results"]))[:n]:
        ref = np.asarray(generate(phase["cfg"], params,
                                  jnp.asarray(req.prompt)[None],
                                  gen_tokens=gen))[0, :res.n_generated]
        got = np.asarray(res.tokens)
        diverge = np.flatnonzero(got != ref)
        agreed += int(diverge[0]) if diverge.size else len(got)
        total += len(got)
        same += int(not diverge.size)
    return same, agreed / max(total, 1)


def stream_agreement(a: dict, b: dict) -> tuple[int, int]:
    """(identical streams, requests) between two phases' results."""
    pairs = list(zip(a["results"], b["results"]))
    return sum(np.array_equal(x.tokens, y.tokens) for x, y in pairs), len(pairs)


def _versions() -> str:
    from importlib.metadata import PackageNotFoundError, version
    out = [f"jax {jax.__version__}"]
    for pkg in ("jaxlib", "libtpu"):
        try:
            out.append(f"{pkg} {version(pkg)}")
        except PackageNotFoundError:
            out.append(f"{pkg} not installed")
    return ", ".join(out)


def _print_phase(name: str, ph: dict) -> None:
    st = ph["stats"]
    print(f"[{name}] set-up {ph['setup_s']:.1f}s (backend compile "
          f"{ph['compile_s']:.1f}s over {ph['compiles']} programs); steady "
          f"{st['generated_tokens']} tokens in {st['wall_s']:.2f}s = "
          f"{st['tok_per_s']:.1f} tok/s, {ph['steady_compiles']} compiles "
          f"in the steady run (one reading, not a benchmark)")
    print(f"[{name}] {st['requests']} requests, {st['decode_steps']} decode "
          f"steps, {st['prefill_chunks']} prefill chunks, prefix hits "
          f"{st.get('prefix_hits', 0)}/"
          f"{st.get('prefix_hits', 0) + st.get('prefix_misses', 0)}, "
          f"problems: {ph['problems'] or 'none'}")


def main() -> int:
    from repro.kernels.ops import default_interpret

    cache_dir = setup_compile_cache()     # before the backend starts
    dev = jax.devices()[0]
    if dev.platform != "tpu" or default_interpret():
        print(f"chip_smoke: needs a TPU with compiled Pallas kernels; found "
              f"platform {dev.platform!r} (interpret={default_interpret()})",
              file=sys.stderr)
        return 2
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: platform={device['platform']} kind={device['kind']} "
          f"count={device['count']}")
    print(f"versions: {_versions()}")
    print(f"compile cache: {cache_dir}")
    clock = CompileClock()
    notes = DispatchNotes()
    log = logging.getLogger("repro.dispatch")
    log.setLevel(logging.INFO)
    log.propagate = False
    log.addHandler(notes)
    checks: dict[str, bool] = {}

    cfg = build_config(build_parser().parse_args(list(SERVE_FLAGS)))
    print(f"model: {cfg.name} {cfg.n_layers}L d_model={cfg.d_model} "
          f"heads={cfg.n_heads}/{cfg.n_kv_heads} head_dim={cfg.head_dim} "
          f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} {cfg.dtype}")

    t0 = time.perf_counter()
    exact = sc_gemm_exactness(cfg, [(cfg.sc_bits, 8), (cfg.sc_bits, 128),
                                    (4, 8)])
    for row in exact:
        print(f"sc-gemm counts {row['shape']} b{row['bits']} pallas_tuned "
              f"vs reference: {'equal' if row['equal'] else 'DIFFER'}")
    print(f"sc-gemm exactness: {time.perf_counter() - t0:.1f}s incl. tuning")
    checks["sc_gemm_counts_exact"] = all(r["equal"] for r in exact)

    params = bind(cfg).init_params(jax.random.PRNGKey(0))
    phases = {}
    for name, extra in PHASES:
        notes.notes.clear()
        phases[name] = ph = run_phase(SERVE_FLAGS + extra, params, clock)
        _print_phase(name, ph)
        checks[f"{name}_drained"] = not ph["problems"]
        for site, path in sorted(notes.notes.items()):
            print(f"[{name}] {site}: {path}")
        if name == "sc-gemm":
            impls = {path for site, path in notes.notes.items()
                     if site.startswith("sc_matmul")}
            checks["sc_gemm_phase_pallas_tuned"] = impls == {"pallas_tuned"}
        if name == "speculative":
            st = ph["stats"]
            same, n = stream_agreement(phases["exact"], ph)
            print(f"[{name}] acceptance {st['spec_acceptance_rate']:.4f}, "
                  f"{st['spec_tokens_per_round']:.3f} tokens/round summed "
                  f"over live slots; streams "
                  f"identical to the exact phase: {same}/{n}")

    err = reference_error(phases["exact"], params)
    checks["reference_logits"] = err <= REF_TOL
    print(f"exact engine vs f32 highest-precision prefill, request 0 "
          f"first-token logits: max|diff|/max|ref| = {err:.3e} "
          f"(tolerance {REF_TOL})")
    same, rate = generate_agreement(phases["exact"], params)
    print(f"exact engine vs generate(): {same}/{AGREEMENT_REQUESTS} streams "
          f"identical, token agreement {rate:.4f} (printed, not gated)")

    failed = [k for k, v in checks.items() if not v]
    print(f"checks: {'all passed' if not failed else 'FAILED ' + str(failed)}")
    print(json.dumps({"ok": not failed, "device": device}))
    return 0 if not failed else 1


if __name__ == "__main__":
    sys.exit(main())
