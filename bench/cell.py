"""One cell, end to end: the configuration and mix it names, set-up, the
window, the readings, and the check of ``correct`` against the plain
reference."""
from __future__ import annotations

import dataclasses
import gc
import importlib
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from . import harness, traffic, work

__all__ = ["CellSpec", "load", "run", "program_config", "reference_gaps",
           "gap_stats", "judge"]

HERE = Path(__file__).resolve().parent

#: Sequences the reference runs at once (one compiled shape per cell).
REF_BLOCK = 4


@dataclasses.dataclass
class CellSpec:
    name: str
    chips: int
    config: dict               # the configuration file
    mix: dict                  # the traffic mix file
    end_to_end: list           # metric entries this cell reports
    per_layer: list


def _reports(entry: dict, cell: str, reported: set | None = None) -> bool:
    if "workloads" in entry:
        return cell in entry["workloads"]
    return reported is None or entry["moves"] in reported


def load(root: Path, workload: str) -> CellSpec:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cell = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        raise SystemExit(f"bench: no workload {workload!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    e2e = [m for m in bench["end_to_end"] if _reports(m, workload)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _reports(m, workload, names)]
    return CellSpec(name=workload, chips=int(cell["chips"]),
                    config=json.loads((root / conf["file"]).read_text()),
                    mix=traffic.load_mix(cell["traffic"]),
                    end_to_end=e2e, per_layer=per_layer)


def reader(name: str):
    """The ``read(run)`` function of ``bench/metrics/<name>.py``."""
    path = HERE / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench.metrics.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def reference_module(config: dict):
    return importlib.import_module(f"bench.reference.{config['reference']}")



# ------------------------------------------------------------ the program

#: (configuration file key, program ``ModelConfig`` field) pairs that must
#: agree before a run is sound.
_SIZE_FIELDS = (("num_hidden_layers", "n_layers"), ("hidden_size", "d_model"),
                ("num_attention_heads", "n_heads"),
                ("num_key_value_heads", "n_kv_heads"),
                ("intermediate_size", "d_ff"), ("vocab_size", "vocab_size"),
                ("rms_norm_eps", "norm_eps"), ("rope_theta", "rope_theta"),
                ("tie_word_embeddings", "tie_embeddings"),
                ("hidden_act", "act"))


def serve_flags(config: dict, mix: dict) -> list[str]:
    """``launch.serve`` flags of the cell's engine."""
    eng = mix["engine"]
    gen = int(mix["output"]["high"])
    flags = ["--arch", config["arch"], "--capacity", str(eng["capacity"]),
             "--block", str(eng["block"]), "--chunk", str(eng["chunk"]),
             "--prompt-len", str(eng["max_seq"] - gen), "--gen", str(gen)]
    if "pages" in eng:
        flags += ["--pages", str(eng["pages"])]
    if not eng.get("prefix_cache", True):
        flags.append("--no-prefix-cache")
    num = config["numerics"]
    if num["projections"] == "sc":
        flags += ["--sc-gemm", "--sc-impl", num["sc_impl"]]
    return flags


def program_config(config: dict, mix: dict):
    """The program's config for this cell: built by ``launch.serve`` from
    the configuration's ``arch``, then set to every size and numeric the
    configuration file states (the program takes them as config fields);
    a field that still differs is an error."""
    from repro.launch.serve import build_config, build_parser
    args = build_parser().parse_args(serve_flags(config, mix))
    model, num = config["model"], config["numerics"]
    want = {f: model[k] for k, f in _SIZE_FIELDS}
    want["head_dim"] = config["derived"]["head_dim"]
    want["dtype"] = model["torch_dtype"]
    want["use_sc_gemm"] = num["projections"] == "sc"
    want["attn_sc"] = num["attention"] != "exact"
    if "sc_bits" in num:
        want["sc_bits"] = int(num["sc_bits"])
    cfg = dataclasses.replace(build_config(args), **want).validate()
    got = {f: getattr(cfg, f) for f in want}
    if got != want:
        raise SystemExit(f"bench: the program's config differs from the "
                         f"configuration file: {got} != {want}")
    return cfg, args


# --------------------------------------------------------------- records

@dataclasses.dataclass
class RunRecord:
    """Everything the metric readers see."""
    cell: CellSpec
    config_file: dict
    mix: dict
    sizes: object
    peak: dict
    window: harness.Window
    tokens: harness.Tokens
    spec_by_uid: dict
    setup_s: float
    trace: object = None


def _device_info(devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


# ------------------------------------------------------------ correctness

def sample_requests(run: RunRecord, seed: int, tokens: int) -> list:
    """Finished requests the reference checks, drawn from the seed: the
    longest finished request, then others until ``tokens`` served tokens
    are in the sample."""
    done = [s for s in run.window.specs
            if len(run.tokens.ids.get(s.uid, ())) >= s.max_new]
    if not done:
        return []
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 7]))
    longest = max(done, key=lambda s: len(s.prompt) + s.max_new)
    rest = [done[i] for i in rng.permutation(len(done))
            if done[i] is not longest]
    out, n = [longest], longest.max_new
    for s in rest:
        if n >= tokens:
            break
        out.append(s)
        n += s.max_new
    return out


def reference_gaps(ref, params, sizes, sample, served, *, max_seq: int,
                   max_new: int, numerics: str, bits: int,
                   control: dict | None = None):
    """Per sampled request, the gap by which each served token's logit lies
    below the reference's best at its position (teacher-forced on the
    served stream). With ``control`` (a configuration's ``control`` group:
    ``numerics`` and, for the multiplier, ``sc_bits``), also the gap under
    the reference of the token the control numeric puts first at each
    position."""
    import jax
    import jax.numpy as jnp

    gaps, ctrl_gaps = [], []
    for b0 in range(0, len(sample), REF_BLOCK):
        block = sample[b0:b0 + REF_BLOCK]
        toks = np.zeros((REF_BLOCK, max_seq), np.int32)
        rows = np.zeros((REF_BLOCK, max_new), np.int32)
        want = np.zeros((REF_BLOCK, max_new), np.int32)
        n_rows = []
        for i, s in enumerate(block):
            ids = served[s.uid][:s.max_new]
            seq = np.concatenate([s.prompt, ids[:-1]]).astype(np.int32)
            toks[i, :len(seq)] = seq
            p = len(s.prompt)
            rows[i, :len(ids)] = np.arange(p - 1, p - 1 + len(ids))
            rows[i, len(ids):] = p - 1
            want[i, :len(ids)] = ids
            n_rows.append(len(ids))
        with jax.default_matmul_precision("highest"):
            lg = ref.logits_at(params, toks, rows, sizes, numerics=numerics,
                               bits=bits)
            best = jnp.max(lg, axis=-1)
            served_logit = jnp.take_along_axis(lg, jnp.asarray(want)[..., None],
                                               axis=-1)[..., 0]
            g = np.asarray(best - served_logit)
            if control:
                cl = ref.logits_at(params, toks, rows, sizes,
                                   numerics=control["numerics"],
                                   bits=int(control.get("sc_bits", bits)))
                pick = jnp.argmax(cl, axis=-1)
                cg = np.asarray(best - jnp.take_along_axis(
                    lg, pick[..., None], axis=-1)[..., 0])
        for i, n in enumerate(n_rows):
            gaps.append(g[i, :n])
            if control:
                ctrl_gaps.append(cg[i, :n])
    return gaps, ctrl_gaps


def gap_stats(gaps: list) -> dict:
    """The numbers ``correct`` may compare, over every checked position:
    the widest and the mean gap of the served token below the reference's
    best logit, and the percent of positions where it is not the best."""
    if not gaps:
        return {"max_logit_gap": math.inf, "mean_logit_gap": math.inf,
                "mismatch_share": 100.0}
    g = np.concatenate(gaps)
    return {"max_logit_gap": float(g.max()),
            "mean_logit_gap": float(g.mean()),
            "mismatch_share": float(100.0 * (g > 0).mean())}


def judge(stats: dict, limits: dict) -> tuple[bool, dict]:
    """Whether every number a configuration's ``limits`` name is within its
    limit (at most it), and each beside its limit. The program's run and
    the control are judged by this one function."""
    compared = {k: {"value": stats[k], "limit": v} for k, v in limits.items()}
    ok = all(v is not None and stats[k] <= v for k, v in limits.items())
    return ok, compared


# ------------------------------------------------------------------- run

def sc_calls_per_module(sizes, capacity: int, chunk: int) -> dict:
    """SC-GEMM calls ``(M, K, N, count)`` one execution of each step program
    makes, at the logical shapes of the ``sc_matmul`` boundary: the batched
    decode computes every slot's row, a prefill chunk its ``chunk`` rows and
    the head for the last one."""
    layer = work.projection_shapes(sizes)

    def calls(m, head_m):
        return ([(m, k, n, sizes.layers) for k, n in layer]
                + [(head_m, sizes.d, sizes.vocab, 1)])
    return {"jit_decode": calls(capacity, capacity),
            "jit_step": calls(chunk, 1)}


def profiler(trace_dir: str):
    """Start/stop callables of a device trace into ``trace_dir``, with the
    Python tracer off (it would record every call of the host loop)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return (lambda: jax.profiler.start_trace(trace_dir,
                                             profiler_options=opts),
            jax.profiler.stop_trace)


def execute(spec: CellSpec, *, seed: int, seconds: float, trace: bool,
            devices, peak: dict, t_process: float, say=print,
            control: bool = False, warm: bool = True,
            warm_marker: Path | None = None,
            records: list | None = None) -> dict:
    """One run of ``spec``: set-up, window, readings, and the reference
    check. Returns the result object (without printing it). The first-run
    warm-up runs when ``warm_marker`` is None or does not exist yet, and
    creates it; ``warm=False`` skips every warm-up (a process that reads
    limits, where the window's timing is not a metric). ``control`` also
    reads the configuration's control on the same sample: the gaps, under
    the reference, of the token the control puts first at each served
    position, judged against the same limits (``result["control"]``, beside
    the program's own in ``result["program"]``). ``records``, when given,
    receives the run's :class:`RunRecord`."""
    import jax

    from repro.launch.serve import build_engine

    from . import tracing
    ref = reference_module(spec.config)
    clock = harness.CompileClock()
    notes = harness.DispatchNotes().attach()
    mix, config = spec.mix, spec.config
    sizes = ref.Sizes(config["model"], config["derived"])
    eng = mix["engine"]

    dtype = config["model"]["torch_dtype"]
    cfg, serve_args = program_config(config, mix)
    params = ref.make_params(sizes, seed, dtype)
    engine = build_engine(cfg, params, serve_args)
    del params
    max_blocks = -(-eng["max_seq"] // eng["block"])
    view = work.gathered_view_bytes(sizes, engine.capacity, max_blocks,
                                    eng["block"], np.dtype(dtype).itemsize)
    say(f"engine: capacity {engine.capacity}, max_seq {eng['max_seq']}, "
        f"block {eng['block']}, chunk {engine.chunk}, buckets "
        f"{engine.buckets}; gathered decode view of every layer "
        f"{view / 2**30:.3f} GiB")

    specs, warm_specs, first = traffic.generate(mix, seed, seconds,
                                                sizes.vocab)
    if not warm:
        warm_specs = []
    elif warm_marker is None or not warm_marker.exists():
        warm_specs = first + warm_specs
    tokens = harness.Tokens()
    c0 = clock.reading()
    n_warm = harness.warm(engine, warm_specs, tokens)
    c1 = clock.reading()
    if warm and warm_marker is not None:
        warm_marker.parent.mkdir(parents=True, exist_ok=True)
        warm_marker.touch()
    say(f"warm-up: {len(warm_specs)} requests in {n_warm} steps, "
        f"{c1[1] - c0[1]} compile events ({c1[0] - c0[0]:.1f} s)")

    by_uid = {s.uid: s for s in specs}
    capacity = engine.capacity
    tokens = harness.Tokens()

    def slots_full(tok: harness.Tokens) -> bool:
        live = sum(1 for uid, t in tok.times.items()
                   if len(t) < by_uid[uid].max_new)
        return live >= capacity

    trace_dir = profile = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        profile = profiler(trace_dir)
    open_loop = mix["loop"] == "open"
    window = harness.drive(
        engine, specs, tokens, seconds=seconds,
        preroll_s=mix["arrivals"]["preroll_s"] if open_loop else 0.0,
        opens=None if open_loop else slots_full,
        open_by=float(mix.get("fill_s", 0.0)),
        drain_s=float(mix["drain_s"]), profile=profile,
        profile_s=min(float(mix["trace_s"]), seconds))
    c2 = clock.reading()
    setup_s = window.start - t_process
    n_in, s_in, names = clock.between(window.start, window.end)
    say(f"window: {window.seconds:.3f} s after {setup_s:.3f} s of set-up; "
        f"{len(window.steps)} steps; compile events (compiles or cache "
        f"loads) inside the window {n_in} ({s_in:.3f} s), from warm-up to "
        f"the end of the drain {c2[1] - c1[1]} ({c2[0] - c1[0]:.2f} s); drain "
        f"{window.stopped - window.end:.2f} s; preemption replays "
        f"{tokens.replays}")
    if names:
        say(f"programs compiled or loaded inside the window: {names[:12]}")
    late = [window.submitted[s.uid] - (window.start + s.arrival)
            for s in specs if open_loop and s.uid in window.submitted
            and 0 <= s.arrival < window.seconds]
    if late:
        say(f"arrival generator lateness: median {np.median(late) * 1e3:.2f} "
            f"ms, max {max(late) * 1e3:.2f} ms over {len(late)} requests due "
            f"in the window")
    for site, path in sorted(notes.notes.items()):
        say(f"dispatch: {site}: {path}")

    device = _device_info(devices)
    record = RunRecord(cell=spec, config_file=config, mix=mix, sizes=sizes,
                       peak=peak, window=window, tokens=tokens,
                       spec_by_uid=by_uid, setup_s=setup_s)
    if records is not None:
        records.append(record)
    if trace:
        record.trace = tracing.reduce(
            trace_dir, window.traced,
            sc_calls_per_module(sizes, capacity, engine.chunk)
            if cfg.use_sc_gemm else None)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = record.trace.busy_s
        device["window_s"] = record.trace.window_s
        say(f"trace: programs run {record.trace.modules}")
    metrics = {}
    for m in (spec.per_layer if trace else spec.end_to_end):
        value = reader(m["name"])(record)
        if value is not None and math.isfinite(value):
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    # -------------------------- the check of correct, with the program freed
    served = {uid: np.asarray(ids, np.int64) for uid, ids in tokens.ids.items()}
    need = int(mix["sample_tokens"])
    sample = sample_requests(record, seed, need)
    engine = None
    gc.collect()
    t_ref = time.perf_counter()
    num = config["numerics"]
    gaps, ctrl = reference_gaps(
        ref, ref.make_params(sizes, seed, dtype), sizes, sample, served,
        max_seq=eng["max_seq"], max_new=int(mix["output"]["high"]),
        numerics="sc" if num["projections"] == "sc" else "exact",
        bits=int(num.get("sc_bits", 8)),
        control=config["control"] if control else None)
    stats = gap_stats(gaps)
    if open_loop:
        due = [s for s in specs if 0 <= s.arrival < window.seconds]
    else:
        due = [s for s in specs if any(window.start <= t < window.end
                                       for t in tokens.times.get(s.uid, ()))]
    no_first = [s.uid for s in due if not tokens.times.get(s.uid)]
    overlong = sum(1 for uid, ids in tokens.ids.items()
                   if len(ids) > by_uid[uid].max_new)
    bad_ids = sum(int(((ids < 0) | (ids >= sizes.vocab)).sum())
                  for ids in served.values())
    checked = int(sum(len(g) for g in gaps))
    within, compared = judge(stats, config["limits"])
    compared.update({
        "served_tokens_checked": {"value": checked, "limit": need},
        "due_requests_without_first_token": {"value": len(no_first),
                                             "limit": 0},
        "overlong_streams": {"value": overlong, "limit": 0},
        "token_ids_outside_vocab": {"value": bad_ids, "limit": 0},
    })
    correct = (within and checked >= need
               and not no_first and not overlong and not bad_ids)
    say(f"reference check: {len(sample)} requests, {checked} served tokens, "
        f"{time.perf_counter() - t_ref:.1f} s; "
        + ", ".join(f"{k} {v:.6g}" for k, v in stats.items()))
    result = {"correct": bool(correct), "attempted": len(due),
              "failed": len(no_first) + len(window.refused),
              "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = record.trace.breakdown()
    if control:
        c_stats = gap_stats(ctrl)
        c_within, _ = judge(c_stats, config["limits"])
        result["control"] = {**c_stats, "correct": c_within}
        result["program"] = {**stats, "correct": bool(correct)}
    result["compared"] = compared
    return result


def run(spec: CellSpec, args, devices, cache_dir: str, t_process: float,
        warm_marker: Path | None = None) -> int:
    """The command line's run: names the device, executes, and prints the
    comparisons (last on standard error) and the result line (last on
    standard output)."""
    dev = devices[0]
    peak = work.peaks(dev.device_kind)
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    print(f"cell {spec.name}: config {spec.config['name']}, traffic "
          f"{spec.mix['name']}, seed {args.seed}, {args.seconds:g} s, trace "
          f"{args.trace}; compile cache {cache_dir}", flush=True)
    result = execute(spec, seed=args.seed, seconds=args.seconds,
                     trace=bool(args.trace), devices=devices, peak=peak,
                     t_process=t_process, warm_marker=warm_marker,
                     say=lambda m: print(m, flush=True))
    for k, v in result["compared"].items():
        print(f"[bench] compared {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
