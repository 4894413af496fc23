"""Serving driver: a thin CLI over the continuous-batching engine
(``repro.serving``, DESIGN.md §7), keeping static batching as an A/B mode
and the sequential per-request :func:`generate` as the bit-exactness
baseline.

    PYTHONPATH=src python -m repro.launch.serve --arch mamba2-130m --reduced \
        --requests 8 --prompt-len 32 --gen 32 [--no-continuous] [--sc-gemm]
"""
from __future__ import annotations

import argparse
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import ARCHS
from repro.models import bind


@functools.lru_cache(maxsize=32)
def _compiled_steps(cfg, gen_tokens: int):
    """Jitted (prefill, decode) pair for a config.

    One pair per (cfg, gen_tokens): the old per-call ``jax.jit(lambda ...)``
    closures created *fresh* jit wrappers on every ``generate`` call, so XLA
    recompiled both steps for every request even at identical shapes. The
    wrappers here live as long as the process and re-trace only on new
    shapes; the serving engine gets the same reuse from
    ``launch.steps.cached_prefill_step``/``cached_decode_step``.
    """
    m = bind(cfg)
    prefill = jax.jit(lambda p, batch: m.prefill_step(
        p, batch, extra_slots=gen_tokens))
    decode = jax.jit(m.decode_step)
    return prefill, decode


def generate(cfg, params, prompts: jnp.ndarray, *, gen_tokens: int,
             temperature: float = 0.0, seed: int = 0):
    """``prompts: (B, S)`` int32 -> (B, gen_tokens) sampled continuations.

    The *sequential* baseline: every sequence decodes ``gen_tokens`` steps
    in lockstep. With B=1 and greedy sampling this is the reference stream
    the serving engine reproduces bit-for-bit (tests/test_serving.py).
    """
    prefill, decode = _compiled_steps(cfg, gen_tokens)
    b, s = prompts.shape[:2]

    logits, cache = prefill(params, {"tokens": prompts})
    key = jax.random.PRNGKey(seed)
    out = []
    tok = None
    for i in range(gen_tokens):
        step_logits = logits[:, -1]
        if cfg.n_codebooks:
            step_logits = step_logits.reshape(b, cfg.n_codebooks, cfg.vocab_size)
        if temperature > 0:
            key, sub = jax.random.split(key)
            tok = jax.random.categorical(sub, step_logits / temperature, axis=-1)
        else:
            tok = jnp.argmax(step_logits, axis=-1)
        tok = tok.astype(jnp.int32)
        out.append(tok)
        batch_tok = tok[:, None] if not cfg.n_codebooks else tok[:, None, :]
        logits, cache = decode(params, cache, {"tokens": batch_tok})
    return jnp.stack(out, axis=1)


def build_parser() -> argparse.ArgumentParser:
    from repro.core.sc_matmul import SC_IMPLS

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8,
                    help="number of requests in the synthetic workload")
    ap.add_argument("--capacity", type=int, default=4,
                    help="slot-pool capacity (decode batch)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32,
                    help="max new tokens per request; the synthetic workload "
                         "mixes lengths in [gen/4, gen] to exercise "
                         "continuous batching")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--no-continuous", action="store_true",
                    help="static batching A/B: admit in gangs, every request "
                         "waits for the gang's slowest")
    ap.add_argument("--no-paged", action="store_true",
                    help="contiguous slot stripes A/B: every slot reserves a "
                         "full max_seq stripe instead of paged blocks")
    ap.add_argument("--block", type=int, default=64,
                    help="paged cache page size in tokens (DESIGN.md §8)")
    ap.add_argument("--pages", type=int, default=None,
                    help="paged cache page budget (n_blocks); default "
                         "capacity * ceil(max_seq / block), i.e. no "
                         "oversubscription — set lower to trade preemptions "
                         "for memory")
    ap.add_argument("--sc-gemm", action="store_true",
                    help="serve through the SC-GEMM numeric (inference "
                         "emulation of the paper's multiplier)")
    ap.add_argument("--sc-impl", choices=SC_IMPLS, default=None,
                    help="SC-GEMM kernel (overrides the config's sc_impl)")
    ap.add_argument("--attn-sc", action="store_true",
                    help="route attention's QK^T/PV contractions through the "
                         "SC popcount path (DESIGN.md §13) at the config's "
                         "sc_bits width")
    ap.add_argument("--attn-sc-bits", type=int, default=None,
                    help="operand bit width for --attn-sc (overrides the "
                         "config's sc_bits; 2..8)")
    ap.add_argument("--paged-attn", choices=("auto", "jnp", "pallas_tuned"),
                    default=None,
                    help="paged decode-attention dispatch (DESIGN.md §9; "
                         "overrides the config's paged_attn_kernel)")
    ap.add_argument("--no-fused-paged", action="store_true",
                    help="paged decode through the gather→decode→commit "
                         "round-trip instead of attending on the page pool "
                         "directly (the memory A/B)")
    ap.add_argument("--prefill-mode", choices=("chunked", "oneshot"),
                    default="chunked",
                    help="chunked: interleave bounded prefill chunks with "
                         "decode steps (DESIGN.md §10); oneshot: whole-prompt "
                         "prefill at admission (the scheduling A/B)")
    ap.add_argument("--chunk", type=int, default=16,
                    help="prefill chunk length in tokens (rounded up to a "
                         "cfg.ssm_chunk multiple for ssm/hybrid)")
    ap.add_argument("--prefill-budget", type=int, default=None,
                    help="prefill tokens per engine step (default: one chunk)")
    ap.add_argument("--prefix-cache", dest="prefix_cache",
                    action=argparse.BooleanOptionalAction, default=True,
                    help="share block-aligned prompt prefixes across "
                         "requests via the copy-on-write prefix cache over "
                         "the paged pool (DESIGN.md §12; active for paged + "
                         "chunked + dense, exact by determinism). "
                         "--no-prefix-cache disables sharing (the reuse A/B)")
    ap.add_argument("--prefix-block-hash", type=int, default=0,
                    help="seed keying the radix tree's chained block hash; "
                         "streams are invariant to it (matches verify raw "
                         "tokens), it only permutes tree keys")
    ap.add_argument("--speculate-k", type=int, default=0,
                    help="self-speculative decoding (DESIGN.md §14): draft "
                         "this many tokens per round through the SC popcount "
                         "path, verify with one exact (k+1)-row window; "
                         "greedy acceptance keeps streams bit-identical. "
                         "0 disables. Requires paged layout, a transformer "
                         "family, and temperature 0")
    ap.add_argument("--draft-bits", type=int, default=4,
                    help="SC operand width (2..8) for the speculative draft "
                         "pass; lower is cheaper but accepts less")
    ap.add_argument("--stream", action="store_true",
                    help="drive the engine through per-request token "
                         "callbacks and print an SSE-style event feed as "
                         "tokens land, instead of waiting for run() to drain")
    return ap


def build_config(args: argparse.Namespace):
    """The served model config the parsed flags describe."""
    import dataclasses

    from repro.launch import apply_numeric_overrides

    cfg = ARCHS[args.arch]
    if args.reduced:
        cfg = cfg.reduced(dtype="float32")
    cfg = apply_numeric_overrides(cfg, sc_gemm=args.sc_gemm,
                                  sc_impl=args.sc_impl)
    if args.paged_attn is not None:
        cfg = dataclasses.replace(cfg,
                                  paged_attn_kernel=args.paged_attn).validate()
    if args.attn_sc or args.attn_sc_bits is not None:
        over = {"attn_sc": True}
        if args.attn_sc_bits is not None:
            over["sc_bits"] = args.attn_sc_bits
        cfg = dataclasses.replace(cfg, **over).validate()
    return cfg


def build_requests(cfg, args: argparse.Namespace) -> list:
    """The synthetic workload: ``args.requests`` prompts of
    ``args.prompt_len`` tokens, new-token budgets mixed in [gen/4, gen]."""
    from repro.serving import Request

    rng = np.random.default_rng(1)

    def tokens(n):
        shape = (n, cfg.n_codebooks) if cfg.n_codebooks else (n,)
        return rng.integers(0, cfg.vocab_size, size=shape, dtype=np.int32)

    # Real traffic shares long system/tool preambles; the synthetic
    # workload mirrors that so the prefix cache has something to share —
    # every prompt opens with the same first half, then diverges.
    preamble = tokens(args.prompt_len // 2)
    gens = rng.integers(max(args.gen // 4, 1), args.gen + 1,
                        size=args.requests)
    requests = [
        Request(uid=f"req-{i}",
                prompt=np.concatenate(
                    [preamble, tokens(args.prompt_len - len(preamble))]),
                max_new_tokens=int(g), temperature=args.temperature, seed=i)
        for i, g in enumerate(gens)
    ]
    return requests


def build_engine(cfg, params, args: argparse.Namespace):
    """The serving engine the parsed flags describe, over ``params``."""
    from repro.serving import Engine

    return Engine(cfg, params, capacity=args.capacity,
                  max_seq=args.prompt_len + args.gen,
                  continuous=not args.no_continuous,
                  paged=not args.no_paged, block=args.block,
                  n_blocks=args.pages, fused=not args.no_fused_paged,
                  prefill_mode=args.prefill_mode, chunk=args.chunk,
                  prefill_budget=args.prefill_budget,
                  prefix_cache=args.prefix_cache,
                  prefix_hash_seed=args.prefix_block_hash,
                  speculate_k=args.speculate_k,
                  draft_bits=args.draft_bits)


def main() -> None:
    from repro.launch import setup_compile_cache

    args = build_parser().parse_args()
    setup_compile_cache()
    cfg = build_config(args)
    params = bind(cfg).init_params(jax.random.PRNGKey(0))
    requests = build_requests(cfg, args)
    engine = build_engine(cfg, params, args)
    t0 = time.time()
    if args.stream:
        # SSE-style feed: one `data:` line per emitted token, as it lands
        # (including bit-identical replays after a preemption). run() then
        # just drains the already-submitted queue and collects stats.
        def on_token(uid, index, tok, reason):
            tail = f" finish={reason}" if reason else ""
            print(f"data: {{uid: {uid}, index: {index}, "
                  f"token: {np.asarray(tok).tolist()}}}{tail}")
        for r in requests:
            engine.submit(r, on_token=on_token)
        results = engine.run()
        results.sort(key=lambda r: int(r.uid.rsplit("-", 1)[1]))
    else:
        results = engine.run(requests)
    dt = time.time() - t0
    st = engine.stats
    pages = (f", pages peak {st['peak_pages']}/{st['n_blocks']}"
             f" (block {st['block']}, {st['preemptions']} preemptions)"
             if st["layout"] == "paged" else "")
    if st.get("prefix_cache"):
        pages += (f", prefix {st['prefix_hits']}/{st['prefix_hits'] + st['prefix_misses']}"
                  f" hits ({st['prefill_tokens_saved']} prefill tokens "
                  f"saved, {st['cow_copies']} CoW)")
    if st.get("speculative"):
        pages += (f", spec k={st['speculate_k']}@{st['draft_bits']}b: "
                  f"{st['spec_acceptance_rate']:.0%} accepted, "
                  f"{st['spec_tokens_per_round']:.2f} tok/round "
                  f"(draft {st['spec_draft_us']:.0f}us "
                  f"verify {st['spec_verify_us']:.0f}us)")
    print(f"[serve] {st['mode']}/{st['layout']}/{st['prefill_mode']}: "
          f"{st['requests']} requests, "
          f"{st['generated_tokens']} tokens in {dt:.1f}s "
          f"({st['tok_per_s']:.1f} tok/s incl. compile), "
          f"{st['decode_steps']} decode steps, "
          f"p50 {st['p50_latency_s'] * 1e3:.0f}ms "
          f"p99 {st['p99_latency_s'] * 1e3:.0f}ms, "
          f"ttft p50 {st['ttft_p50_s'] * 1e3:.0f}ms "
          f"itl p50 {st['itl_p50_s'] * 1e3:.1f}ms, "
          f"max decode gap {st['max_decode_gap_s'] * 1e3:.0f}ms "
          f"({st['prefill_chunks']} prefill chunks, "
          f"{st['prefill_executables']} executables / "
          f"{len(st['buckets'])} buckets){pages}")
    print(f"[serve] first stream: {results[0].tokens[:16]}")


if __name__ == "__main__":
    main()
