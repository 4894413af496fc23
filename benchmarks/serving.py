"""Serving-engine benchmark: continuous vs static batching on a synthetic
mixed-length workload, recording tok/s, p50/p99 request latency, decode
steps, and paged-cache page usage into the ``BENCH_serving.json``
trajectory.

    PYTHONPATH=src python -m benchmarks.serving [--smoke] [--json PATH]

Rows encode throughput as ``us_per_call`` = µs per *generated token*
(1e6 / tok/s), so ``benchmarks.check_regression`` gates a >2x tok/s drop with
the exact machinery that gates the SC-GEMM kernel rows: lower is better,
matching-signature baselines, noise floor. Timed serving rows also carry
``ttft_p50_ms`` / ``itl_p50_ms`` as first-class columns — time to first
token and inter-token latency, the two numbers a streaming caller feels —
which the gate treats as informational (only ``us_per_call`` is compared).
``derived`` carries the remaining human numbers (tok/s, latency
percentiles, decode steps, pages in use).

A second, gate-exempt marker row records the **long-tail acceptance**
(ISSUE 4 / DESIGN.md §8): a workload whose tail request exceeds the
per-slot stripe of a contiguous pool under a fixed token budget — the
contiguous engine must refuse it with ``PoolExhausted`` while the paged
engine drains it inside the same budget by giving the tail many pages and
the short requests few.

A gate-exempt marker row records the **chunked-vs-one-shot prefill A/B**
(ISSUE 6 / DESIGN.md §10) on a varied-prompt-length workload: one-shot
admission stalls the whole decode batch for a full-prompt forward, while
chunked prefill bounds the worst gap between consecutive decode steps to
roughly one chunk (the ``engine.step`` spans of a profiler trace show
it). The row asserts that both modes generate bit-identical streams and
that the prompt-bucket set bounds the number of chunked-prefill executables
(``prefill_executables <= len(buckets)``), so the smoke CI job fails if
bucketing ever starts compiling per prompt length.

A gate-exempt marker row records the **prefix-cache A/B** (ISSUE 8 /
DESIGN.md §12): a shared-prefix workload — many requests over two long
common prompts plus divergent-tail variants — served with the
copy-on-write prefix cache on and off. The row hard-asserts that both
serve bit-identical streams (sharing is exact, not approximate, because
the SC multiplier is deterministic), that the cache actually shared work
(``hit_rate > 0``, ``prefill_tokens_saved > 0``, at least one CoW copy
from the chunk-aligned resume landing mid-page), and that TTFT p50 with
the cache on is no worse than off — then records both TTFT numbers.

A third, gate-exempt marker row records the **gather-vs-fused decode A/B**
(ISSUE 5 / DESIGN.md §9): the same paged workload through the PR 4
gather → decode → commit round-trip and through the fused paged-attention
path, with µs/token for both and the *peak decode transient* each implies —
the gather path materializes a dense ``capacity × max_blocks·block`` view
of every K/V leaf per step (bytes computed from the abstract cache tree),
while the fused kernel's working set is its VMEM scratch, sized by one
sequence's pages and independent of capacity.

A gate-exempt marker row records the **exact-vs-SC attention A/B**
(DESIGN.md §13): the same paged workload served with exact f32 attention
and with ``attn_sc`` routing QK^T/PV through the bit-parallel popcount
multiplier. The row hard-asserts that *each* mode's engine streams are
bit-identical to its own sequential per-request baseline (the SC score
path must keep the batch-composition invariance the engine's exactness
story rests on), then records µs/token for both plus the per-bits
output/score divergence of the SC path from ``sc_attention_divergence``.

A gate-exempt marker row records the **self-speculative decoding A/B**
(ISSUE 10 / DESIGN.md §14): a shared-prefix smoke workload served without
speculation and with ``speculate_k`` draft tokens per round proposed by
the SC popcount path and verified by one exact (k+1)-row window. The row
hard-asserts that the speculative streams are bit-identical to the
sequential per-request baseline (greedy acceptance emits only exact-path
argmaxes, so speculation is a pure scheduling change) and that the draft
actually earned something (``acceptance_rate > 0``), then records the
tok/s speedup over the non-speculative engine plus the acceptance and
draft/verify timing columns. The speedup is structural on CPU — the SC
draft is *emulated* here, so the ratio reflects step-count savings, not
the multiplier's silicon win.

The workload is deterministic (fixed seeds, greedy sampling) and each mode
is measured on its second run — the first run pays XLA compilation for the
prefill/decode executables, which the compiled-step caches
(``launch.steps.cached_*``) then reuse.
"""
from __future__ import annotations

import argparse
from pathlib import Path

import numpy as np

REPO_ROOT = Path(__file__).resolve().parent.parent
DEFAULT_TRAJECTORY = REPO_ROOT / "BENCH_serving.json"

#: (requests, capacity, prompt_len, max_gen)
SMOKE = (8, 4, 16, 8)
FULL = (32, 8, 64, 48)


def _requests(cfg, n: int, prompt_len: int, max_gen: int):
    """Bimodal mixed-length workload: alternating short/long generations —
    the adversarial case for static batching, where every short request
    waits out its gang's longest neighbour."""
    from repro.serving import Request

    rng = np.random.default_rng(7)
    shape = ((prompt_len, cfg.n_codebooks) if cfg.n_codebooks
             else (prompt_len,))
    short = max(max_gen // 4, 1)
    return [Request(uid=f"bench-{i}",
                    prompt=rng.integers(0, cfg.vocab_size, size=shape,
                                        dtype=np.int32),
                    max_new_tokens=short if i % 2 == 0 else max_gen)
            for i in range(n)]


def run(smoke: bool = False, arch: str = "smollm-360m") -> list[dict]:
    import jax

    from repro.configs.registry import ARCHS
    from repro.models import bind
    from repro.serving import Engine, default_serving_mesh

    n, capacity, prompt_len, max_gen = SMOKE if smoke else FULL
    cfg = ARCHS[arch].reduced(dtype="float32")
    params = bind(cfg).init_params(jax.random.PRNGKey(0))
    mesh = default_serving_mesh()   # shared -> both modes reuse executables
    max_seq = prompt_len + max_gen

    rows = []
    stats = {}
    for continuous in (True, False):
        mode = "continuous" if continuous else "static"
        for measured in (False, True):   # first run compiles, second times
            engine = Engine(cfg, params, capacity=capacity, max_seq=max_seq,
                            mesh=mesh, continuous=continuous)
            engine.run(_requests(cfg, n, prompt_len, max_gen))
            st = engine.stats
        stats[mode] = st
        pages = (f" peak_pages={st['peak_pages']}/{st['n_blocks']}"
                 f" block={st['block']}"
                 f" preemptions={st['preemptions']}"
                 if st.get("layout") == "paged" else "")
        rows.append({
            "name": f"serving/{mode}/{cfg.name}",
            "us_per_call": round(1e6 / st["tok_per_s"], 1),
            "ttft_p50_ms": round(st["ttft_p50_s"] * 1e3, 1),
            "itl_p50_ms": round(st["itl_p50_s"] * 1e3, 2),
            "derived": (f"tok_s={st['tok_per_s']:.1f}"
                        f" p50_ms={st['p50_latency_s'] * 1e3:.0f}"
                        f" p99_ms={st['p99_latency_s'] * 1e3:.0f}"
                        f" ttft_p99_ms={st['ttft_p99_s'] * 1e3:.0f}"
                        f" itl_p99_ms={st['itl_p99_s'] * 1e3:.2f}"
                        f" decode_steps={st['decode_steps']}"
                        f" requests={st['requests']}"
                        f" capacity={capacity}{pages}"),
        })
    # scheduling quality marker (us_per_call=0 rows are gate-exempt): the
    # whole point of the engine — same workload, fewer batched decode steps
    cont, stat = stats["continuous"], stats["static"]
    rows.append({
        "name": f"serving/step_ratio/{cfg.name}",
        "us_per_call": 0.0,
        "derived": (f"continuous={cont['decode_steps']}"
                    f" static={stat['decode_steps']}"
                    f" ratio={cont['decode_steps'] / max(stat['decode_steps'], 1):.2f}"),
    })
    rows.append(_chunked_row(cfg, params, mesh, capacity, prompt_len,
                             max_gen))
    rows.append(_longtail_row(cfg, params, mesh, capacity, prompt_len,
                              max_gen))
    rows.append(_fused_row(cfg, params, mesh, n, capacity, prompt_len,
                           max_gen))
    rows.append(_prefix_row(cfg, params, mesh, n, capacity, prompt_len,
                            max_gen))
    rows.append(_sc_attention_row(cfg, params, mesh, n, capacity, prompt_len,
                                  max_gen))
    rows.append(_speculative_row(cfg, params, mesh, n, capacity, prompt_len,
                                 max_gen))
    return rows


def _speculative_row(cfg, params, mesh, n: int, capacity: int,
                     prompt_len: int, max_gen: int) -> dict:
    """Self-speculative decoding A/B marker (gate-exempt): the same
    shared-prefix workload served without speculation and with a k-token
    SC-drafted / exact-verified round (DESIGN.md §14). Hard-asserted: the
    speculative streams reproduce the sequential per-request baseline
    bit-for-bit (acceptance only reshuffles *when* exact tokens land, never
    *which*), and the draft accepts at least one proposal. Timed on the
    second run of each mode; the speedup column is step-count structure,
    not a silicon claim — the SC draft is emulated on the host here."""
    import jax.numpy as jnp

    from repro.launch.serve import generate
    from repro.serving import Engine, Request

    k, bits = 3, 8
    max_seq = prompt_len + max_gen
    gen = max(max_gen // 2, 1)

    def shaped(s):
        return (s, cfg.n_codebooks) if cfg.n_codebooks else (s,)

    def requests():
        # shared preamble + divergent tails: the serve.py traffic shape,
        # so speculation composes with the prefix cache in the measurement
        rng = np.random.default_rng(29)
        pre = rng.integers(0, cfg.vocab_size, size=shaped(prompt_len // 2),
                           dtype=np.int32)
        return [Request(uid=f"spec-{i}",
                        prompt=np.concatenate(
                            [pre, rng.integers(
                                0, cfg.vocab_size,
                                size=shaped(prompt_len - len(pre)),
                                dtype=np.int32)]),
                        max_new_tokens=gen)
                for i in range(n)]

    stats = {}
    for label, spec_k in (("baseline", 0), ("spec", k)):
        for _ in range(2):             # first run compiles, second times
            engine = Engine(cfg, params, capacity=capacity, max_seq=max_seq,
                            mesh=mesh, speculate_k=spec_k, draft_bits=bits)
            results = engine.run(requests())
        stats[label] = engine.stats
        for req, res in zip(requests(), results):
            baseline = np.asarray(generate(
                cfg, params, jnp.asarray(req.prompt)[None],
                gen_tokens=req.max_new_tokens))[0]
            np.testing.assert_array_equal(
                res.tokens, baseline,
                err_msg=f"{label} engine stream diverged from its "
                        f"sequential baseline at {res.uid}")
    st = stats["spec"]
    assert st["speculative"] and st["spec_rounds"] > 0
    assert st["spec_acceptance_rate"] > 0, \
        "SC draft never had a proposal accepted by exact verification"
    speedup = st["tok_per_s"] / max(stats["baseline"]["tok_per_s"], 1e-9)
    return {
        "name": f"serving/speculative/{cfg.name}",
        "us_per_call": 0.0,
        "derived": (
            f"speedup={speedup:.2f}x"
            f" spec_us_per_tok={1e6 / st['tok_per_s']:.1f}"
            f" base_us_per_tok={1e6 / stats['baseline']['tok_per_s']:.1f}"
            f" k={k} draft_bits={bits}"
            f" acceptance_rate={st['spec_acceptance_rate']:.2f}"
            f" tok_per_round={st['spec_tokens_per_round']:.2f}"
            f" rounds={st['spec_rounds']}"
            f" base_decode_steps={stats['baseline']['decode_steps']}"
            f" requests={n} capacity={capacity}"),
    }


def _sc_attention_row(cfg, params, mesh, n: int, capacity: int,
                      prompt_len: int, max_gen: int) -> dict:
    """Exact-vs-SC attention A/B marker (gate-exempt): the same workload
    served with exact attention and with the SC popcount score path
    (DESIGN.md §13). Hard-asserted: each mode's engine streams reproduce
    its own sequential per-request baseline bit-for-bit — SC attention
    must preserve the batch-composition invariance, not just be "close".
    Timed on the second run of each mode; the per-bits error columns come
    from the ref-oracle divergence probe, not the serving run."""
    import dataclasses

    import jax.numpy as jnp

    from repro.core.error_analysis import sc_attention_divergence
    from repro.launch.serve import generate
    from repro.serving import Engine

    max_seq = prompt_len + max_gen
    stats = {}
    for label, eng_cfg in (
            ("exact", cfg),
            ("sc", dataclasses.replace(cfg, attn_sc=True).validate())):
        for _ in range(2):             # first run compiles, second times
            engine = Engine(eng_cfg, params, capacity=capacity,
                            max_seq=max_seq, mesh=mesh)
            results = engine.run(_requests(cfg, n, prompt_len, max_gen))
        stats[label] = engine.stats
        for req, res in zip(_requests(cfg, n, prompt_len, max_gen), results):
            baseline = np.asarray(generate(
                eng_cfg, params, jnp.asarray(req.prompt)[None],
                gen_tokens=req.max_new_tokens))[0]
            np.testing.assert_array_equal(
                res.tokens, baseline,
                err_msg=f"{label} engine stream diverged from its "
                        f"sequential baseline at {res.uid}")
    err = " ".join(
        f"b{d['bits']}_out_mad={d['output_mad']:.4f}"
        f" b{d['bits']}_score_mad={d['score_mad']:.3f}"
        for d in (sc_attention_divergence(b) for b in (4, 6, 8)))
    return {
        "name": f"serving/sc_attention/{cfg.name}",
        "us_per_call": 0.0,
        "derived": (
            f"exact_us_per_tok={1e6 / stats['exact']['tok_per_s']:.1f}"
            f" sc_us_per_tok={1e6 / stats['sc']['tok_per_s']:.1f}"
            f" sc_bits={cfg.sc_bits} {err}"
            f" requests={n} capacity={capacity}"),
    }


def _prefix_row(cfg, params, mesh, n: int, capacity: int, prompt_len: int,
                max_gen: int) -> dict:
    """Prefix-cache A/B marker (gate-exempt): the workload the cache exists
    for — ``n`` requests over two long common prompts (plus divergent-tail
    variants), so most admissions can attach already-computed prompt pages
    instead of re-prefilling. ``block > chunk`` puts the chunk-aligned
    resume point mid-page on full-prompt hits, forcing the copy-on-write
    path into the measurement. Hard-asserted: streams bit-identical cache
    on vs off, work actually shared, and TTFT p50 no worse with the cache
    on (it should be far better — hits prefill one chunk, misses eight).
    Timed on the second run of each mode (first pays XLA compilation)."""
    from repro.serving import Engine, Request

    plen = 4 * prompt_len                # long prompts: sharing is the win
    chunk = max(prompt_len // 2, 4)
    block = prompt_len                   # block > chunk => mid-page resume
    max_seq = plen + max_gen
    gen = max(max_gen // 2, 1)

    def shaped(s):
        return (s, cfg.n_codebooks) if cfg.n_codebooks else (s,)

    base_rng = np.random.default_rng(13)
    bases = [base_rng.integers(0, cfg.vocab_size, size=shaped(plen),
                               dtype=np.int32) for _ in range(2)]

    def requests():
        rng = np.random.default_rng(17)
        out = []
        for i in range(n):
            base = bases[i % 2]
            if i % 4 == 3:               # shared head, divergent tail
                tail = rng.integers(0, cfg.vocab_size,
                                    size=shaped(plen // 2), dtype=np.int32)
                prompt = np.concatenate([base[:plen // 2], tail])
            else:                        # the common prompt, verbatim
                prompt = base.copy()
            out.append(Request(uid=f"px-{i}", prompt=prompt,
                               max_new_tokens=gen))
        return out

    stats, streams = {}, {}
    for label, enabled in (("off", False), ("on", True)):
        for _ in range(2):               # first run compiles, second times
            engine = Engine(cfg, params, capacity=capacity, max_seq=max_seq,
                            mesh=mesh, block=block, chunk=chunk,
                            prefix_cache=enabled)
            results = engine.run(requests())
        stats[label] = engine.stats
        streams[label] = [r.tokens.tolist() for r in results]
    assert streams["on"] == streams["off"], \
        "prefix cache changed a token stream vs the cache-off baseline"
    st = stats["on"]
    assert st["prefix_cache"] and not stats["off"]["prefix_cache"]
    assert st["prefix_hit_rate"] > 0, "shared-prefix workload never hit"
    assert st["prefill_tokens_saved"] > 0, "hits saved no prefill work"
    assert st["cow_copies"] >= 1, \
        "mid-page resume never exercised copy-on-write"
    ttft_on = st["ttft_p50_s"] * 1e3
    ttft_off = stats["off"]["ttft_p50_s"] * 1e3
    assert ttft_on <= ttft_off, \
        (f"prefix cache made TTFT worse: p50 {ttft_on:.1f}ms on vs "
         f"{ttft_off:.1f}ms off")
    return {
        "name": f"serving/prefix_cache/{cfg.name}",
        "us_per_call": 0.0,
        "derived": (f"hit_rate={st['prefix_hit_rate']:.2f}"
                    f" prefill_tokens_saved={st['prefill_tokens_saved']}"
                    f" cow_copies={st['cow_copies']}"
                    f" reclaims={st['prefix_reclaims']}"
                    f" ttft_p50_ms_on={ttft_on:.1f}"
                    f" ttft_p50_ms_off={ttft_off:.1f}"
                    f" prompt_len={plen} block={block} chunk={chunk}"
                    f" requests={n} capacity={capacity}"),
    }


def _chunked_row(cfg, params, mesh, capacity: int, prompt_len: int,
                 max_gen: int) -> dict:
    """Chunked-vs-one-shot prefill marker (gate-exempt): a varied-length
    long-prompt workload where one-shot admission stalls every live decode
    slot for a whole-prompt forward, while chunked prefill interleaves —
    at most one chunk of prefill per decode step. Hard-asserted, not
    timed: both modes emit bit-identical streams, and the chunked
    executable count stays bounded by the bucket set even though the
    workload has more distinct prompt lengths than buckets get used."""
    from repro.serving import Engine, Request

    chunk = max(prompt_len // 2, 4)
    lens = [4 * prompt_len, prompt_len, 2 * prompt_len, prompt_len + 3,
            3 * prompt_len, prompt_len // 2 + 1]
    max_seq = max(lens) + max_gen

    def requests():
        rng = np.random.default_rng(23)
        out = []
        for i, s in enumerate(lens + lens):
            shape = (s, cfg.n_codebooks) if cfg.n_codebooks else (s,)
            out.append(Request(
                uid=f"chunk-{i}",
                prompt=rng.integers(0, cfg.vocab_size, size=shape,
                                    dtype=np.int32),
                max_new_tokens=max_gen))
        return out

    stats, streams = {}, {}
    for mode in ("oneshot", "chunked"):
        for _ in range(2):             # first run compiles, second times
            engine = Engine(cfg, params, capacity=capacity, max_seq=max_seq,
                            mesh=mesh, prefill_mode=mode, chunk=chunk)
            results = engine.run(requests())
        stats[mode] = engine.stats
        streams[mode] = [r.tokens.tolist() for r in results]
    assert streams["chunked"] == streams["oneshot"], \
        "chunked prefill changed a token stream vs one-shot"
    st = stats["chunked"]
    assert st["prefill_executables"] <= len(st["buckets"]), \
        (f"prompt bucketing failed to bound compilation: "
         f"{st['prefill_executables']} chunked-prefill executables > "
         f"{len(st['buckets'])} buckets")
    return {
        "name": f"serving/chunked_prefill/{cfg.name}",
        "us_per_call": 0.0,
        "derived": (
            f"chunk={st['chunk']}"
            f" prefill_chunks={st['prefill_chunks']}"
            f" executables={st['prefill_executables']}"
            f"/{len(st['buckets'])}buckets"
            f" prompt_lens={len(set(lens))}"
            f" ttft_p50_ms={st['ttft_p50_s'] * 1e3:.0f}"
            f" itl_p50_ms={st['itl_p50_s'] * 1e3:.2f}"),
    }


def _gather_transient_bytes(cfg, capacity: int, block: int,
                            n_blocks: int, max_blocks: int) -> int:
    """Bytes of the dense per-step view the gather path materializes: the
    sum over K/V sequence leaves of the gathered ``(lead, capacity,
    max_blocks·block, *tail)`` shapes — computed on the abstract cache
    tree, so it is exactly what ``paged_gather`` would allocate."""
    import functools

    import jax
    import jax.numpy as jnp

    from repro.models import bind, cache_ops

    m = bind(cfg)
    data_abs = jax.eval_shape(
        lambda: cache_ops.paged_init(m.init_cache, capacity, n_blocks, block))
    tables_abs = jax.ShapeDtypeStruct((capacity, max_blocks), jnp.int32)
    dense_abs = jax.eval_shape(
        functools.partial(cache_ops.paged_gather, block=block),
        data_abs, tables_abs)
    paged_leaves = jax.tree_util.tree_leaves(data_abs)
    dense_leaves = jax.tree_util.tree_leaves(dense_abs)
    return sum(d.size * d.dtype.itemsize
               for d, p in zip(dense_leaves, paged_leaves)
               if d.shape != p.shape)


def _fused_row(cfg, params, mesh, n: int, capacity: int, prompt_len: int,
               max_gen: int) -> dict:
    """Gather-vs-fused decode marker (gate-exempt): µs/token for the two
    paged decode structures on the same workload, plus the peak decode
    transient each implies. The fused engine forces the Pallas kernel
    (interpret mode on CPU — the timing is structural, not a TPU claim;
    the transient bytes are the acceptance signal: gather scales with
    capacity × max_seq, the kernel's VMEM scratch does not)."""
    import dataclasses

    from repro.kernels.autotune import PagedFlashConfig
    from repro.serving import Engine, PagedSlotPool

    max_seq = prompt_len + max_gen
    block = max(max_seq // 4, 1)       # multi-page tables: a real table walk
    block, max_blocks, n_blocks = PagedSlotPool.plan(capacity, max_seq,
                                                     block, None)
    stats = {}
    for label, eng_cfg, fused in (
            ("gather", cfg, False),
            ("fused", dataclasses.replace(
                cfg, paged_attn_kernel="pallas_tuned").validate(), True)):
        for _ in range(2):             # first run compiles, second times
            engine = Engine(eng_cfg, params, capacity=capacity,
                            max_seq=max_seq, mesh=mesh, block=block,
                            n_blocks=n_blocks, fused=fused)
            engine.run(_requests(cfg, n, prompt_len, max_gen))
        stats[label] = engine.stats
    gather_bytes = _gather_transient_bytes(cfg, capacity, block, n_blocks,
                                           max_blocks)
    g = max(cfg.n_heads // max(cfg.n_kv_heads, 1), 1)
    fused_bytes = PagedFlashConfig(kvh=1).vmem_bytes(
        max_blocks=max_blocks, block=block, g=g, d=cfg.head_dim)
    return {
        "name": f"serving/fused_paged/{cfg.name}",
        "us_per_call": 0.0,
        "derived": (
            f"gather_us_per_tok={1e6 / stats['gather']['tok_per_s']:.1f}"
            f" fused_us_per_tok={1e6 / stats['fused']['tok_per_s']:.1f}"
            f" gather_transient_bytes={gather_bytes}"
            f" fused_scratch_bytes={fused_bytes}"
            f" transient_ratio={gather_bytes / max(fused_bytes, 1):.1f}x"
            f" capacity={capacity} block={block} n_blocks={n_blocks}"),
    }


def _longtail_row(cfg, params, mesh, capacity: int, prompt_len: int,
                  max_gen: int) -> dict:
    """Long-tail acceptance under one shared token budget (gate-exempt
    marker row): the contiguous pool (per-slot stripe = budget / capacity)
    must refuse the tail request; the paged pool must drain everything
    without ever holding more pages than the budget."""
    from repro.serving import Engine, PoolExhausted, Request

    stripe = prompt_len + max_gen
    budget_tokens = capacity * stripe
    block = max(stripe // 4, 1)
    long_gen = 2 * stripe - prompt_len          # needs 2 stripes of cache
    shape = ((prompt_len, cfg.n_codebooks) if cfg.n_codebooks
             else (prompt_len,))

    def requests():
        rng = np.random.default_rng(11)
        return [Request(uid=f"tail-{i}",
                        prompt=rng.integers(0, cfg.vocab_size, size=shape,
                                            dtype=np.int32),
                        max_new_tokens=(long_gen if i == 0
                                        else max(max_gen // 4, 1)))
                for i in range(capacity + 2)]

    contiguous = Engine(cfg, params, capacity=capacity, max_seq=stripe,
                        mesh=mesh, paged=False)
    try:
        contiguous.run(requests())
        contiguous_out = "UNEXPECTEDLY-FIT"
    except PoolExhausted:
        contiguous_out = "PoolExhausted"

    paged = Engine(cfg, params, capacity=capacity, max_seq=2 * stripe,
                   mesh=mesh, paged=True, block=block,
                   n_blocks=budget_tokens // block)
    results = paged.run(requests())
    st = paged.stats
    drained = all(r.n_generated == r_req.max_new_tokens
                  for r, r_req in zip(results, requests()))
    return {
        "name": f"serving/longtail/{cfg.name}",
        "us_per_call": 0.0,
        "derived": (f"contiguous={contiguous_out}"
                    f" paged={'drained' if drained else 'INCOMPLETE'}"
                    f" budget_tokens={budget_tokens}"
                    f" peak_pages={st['peak_pages']}/{st['n_blocks']}"
                    f" block={st['block']}"
                    f" preemptions={st['preemptions']}"
                    f" decode_steps={st['decode_steps']}"),
    }


def main() -> None:
    import sys

    from .run import append_trajectory

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small workload / reduced config (CI)")
    ap.add_argument("--json", type=Path, default=DEFAULT_TRAJECTORY,
                    help="serving trajectory file (default: repo root)")
    ap.add_argument("--arch", default="smollm-360m")
    args = ap.parse_args()

    from repro.launch import setup_compile_cache
    setup_compile_cache()

    rows = run(smoke=args.smoke, arch=args.arch)
    print("name,us_per_call,ttft_p50_ms,itl_p50_ms,derived")
    for row in rows:
        print(f"{row['name']},{row['us_per_call']},"
              f"{row.get('ttft_p50_ms', '')},{row.get('itl_p50_ms', '')},"
              f"{str(row['derived']).replace(',', ';')}")
    try:
        append_trajectory(args.json, rows, smoke=args.smoke)
        print(f"serving/trajectory,0,appended to {args.json.name}",
              file=sys.stderr)
    except OSError as e:
        print(f"serving/trajectory,0,NOT appended ({type(e).__name__}: {e})",
              file=sys.stderr)


if __name__ == "__main__":
    main()
