"""Continuous-batching serving engine (DESIGN.md §7–§10, §12, §14).

The engine is a **step scheduler**: one public :meth:`Engine.step` advances
the whole pool by one scheduling quantum — a bounded budget of
prefill-chunk work, completed-prefill admission, then one batched decode
over every live slot — and :meth:`Engine.run` / :meth:`Engine.stream` are
just loops over it.

* *Chunked prefill (default)*: a prompt is prefilled ``chunk`` tokens at a
  time into a B=1 *staging* cache of its prompt-bucket extent
  (``launch.steps.prompt_buckets`` — pow2-style chunk multiples, so the
  compiled-executable count is bounded by the bucket set, not the prompt
  distribution). Each engine step spends at most ``prefill_budget`` tokens
  (default: one chunk) on the staging prompt before decoding, so admission
  never stalls batched decode for more than one chunk — the one-shot
  prefill stall this replaces is the whole-prompt forward between two
  decode steps. On the final chunk the staging cache is admitted through
  the same ``paged_insert`` path a one-shot prefill uses, with the prompt
  length, which writes the rows past it as zeros (one jitted admission per
  bucket; the contiguous pool's ``slot_insert`` takes the extent cut by
  ``cache_ops.truncate_seq``), so pool page accounting and every paging
  invariant (DESIGN.md §8) are untouched. ``prefill_mode="oneshot"`` keeps the
  whole-prompt ``cached_prefill_step`` admission as the scheduling A/B.
* *Prefix cache (paged + chunked + dense or MoE)*: before staging a
  prompt, the engine consults a token-hash radix tree (``serving.prefix``,
  DESIGN.md §12) mapping block-aligned prompt prefixes to pages already
  resident in the pool. On a hit the matched pages are pinned, the staging cache is
  *seeded* with their K/V and enters the chunked-prefill carry at the
  resume offset — only the divergent suffix is computed — and admission
  attaches the block table to the shared pages (copy-on-write for the
  page holding the resume point). Sharing is gated to the dense and MoE
  families (``PREFIX_FAMILIES``): ssm/hybrid recurrent state lives in O(1)
  slot leaves the page pool never captures, so a cached prefix cannot
  restore it.
* *Grow (paged only)*: before each decode step, every live slot's next
  write position must map to an allocated page — and be *writable*: a
  shared or prefix-retained page is copied before the first write lands
  (``PagedSlotPool.ensure_page``). Exhaustion preempts
  youngest-first — including an in-flight staging prefill, whose request
  is re-queued with its partial progress discarded (determinism makes the
  restarted stream bit-identical).
* *Decode (batched)*: one ``cached_paged_decode_step`` (or
  ``cached_decode_step``) call advances all live slots a token. The
  device reduces each row to its greedy token, and the host fetches those
  ids, never the logits, unless a request samples (temperature > 0). The
  tokens are *streamed* — pushed through per-request ``on_token``
  callbacks the moment they exist, or pulled through the
  :meth:`Engine.stream` generator, which drives ``step()`` on demand.
* *Speculate (opt-in)*: with ``speculate_k > 0`` the decode step becomes a
  draft → verify → rollback round (DESIGN.md §14): k cheap SC-numeric
  decode sub-steps at ``draft_bits`` propose tokens, one exact (k+1)-row
  verify window checks them, and greedy acceptance emits the longest
  exactly-matching prefix plus one exact token — so each round yields
  1..k+1 tokens of the *same* bit-identical stream.
* *Evict*: a request leaves on EOS or length; its slot (and pages) free on
  the same step.

Determinism invariant: with SC-GEMM enabled, the engine's per-request
token streams are **bit-identical** to the sequential per-request
``launch.serve.generate`` baseline — for every family, both cache layouts,
and both prefill modes. Chunked prefill preserves it because every chunk
boundary is a multiple of ``cfg.ssm_chunk`` (the SSD recurrence splits
exactly), attention K/V rows are per-row computations scattered at
absolute positions, and the bucket's padding columns are causally masked
into exact no-ops — the invariant tests/test_serving.py sweeps and
tests/test_paging.py fuzzes.

Observability: the work of a step is wrapped in ``engine.*`` host spans
(``jax.profiler.TraceAnnotation``; a span is a cheap no-op unless a profiler
trace is running, and then lands on the trace's host plane beside the
device's operations), and :meth:`Engine.counters` returns cumulative totals
that a caller snapshots between steps. ``engine.sync`` wraps every blocking
``jax.device_get``: the only span in which the host waits on the device.
The spans of one request carry its ``uid``.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Callable, Iterator, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.errors import ConfigError, EngineInvariantError
from repro.launch.steps import (bucket_for, cached_chunked_prefill_step,
                                cached_decode_step, cached_draft_loop_step,
                                cached_paged_decode_step, cached_prefill_step,
                                cached_rollback_step,
                                cached_verify_window_step, prompt_buckets)
from repro.models import bind, cache_ops

from .prefix import PrefixCache, PrefixMatch
from .queue import Request, RequestQueue, RequestResult
from .slots import PagedSlotPool, PoolExhausted, SlotEntry, SlotPool

__all__ = ["Engine", "default_serving_mesh"]

span = jax.profiler.TraceAnnotation

#: Families whose whole per-request state is K/V (or latent) pages plus a
#: position, and whose output for a token depends on that token's own
#: context alone (MoE routing is dropless and per token), so a cached
#: prefix's pages stand in exactly for recomputing it.
PREFIX_FAMILIES = ("dense", "moe")

#: The totals :meth:`Engine.counters` keeps itself (the rest it reads from
#: the pool and the queue).
_COUNTS = ("steps", "decode_rows", "prefill_chunks", "prefill_rows",
           "prefills", "prompt_tokens", "prefix_hits", "prefix_misses",
           "prefill_tokens_saved", "preemptions", "nonfinite_logit_rows",
           "device_sampled_rows", "logit_rows_fetched", "spec_rounds",
           "spec_drafted_tokens", "spec_accepted_tokens",
           "spec_emitted_tokens")

#: ``on_token(uid, index, token, finished_reason)`` — ``index`` is the
#: 0-based position in the generated stream; ``finished_reason`` is None
#: until the final token ("eos" / "length"). A preempted-and-readmitted
#: request *replays* its stream from index 0 (bit-identically); pull-side
#: consumers (``Engine.stream``) dedupe by index.
TokenCallback = Callable[[str, int, np.ndarray, "str | None"], None]


@jax.jit
def _reduce_rows(logits, fetch):
    """``(B, 1, ..., V)`` logits -> each row's argmax over the last axis
    (int32, the first maximum, or the first NaN where there is one, as
    ``np.argmax``), whether the row is all finite, and the last-position
    logits of the rows ``fetch`` names (``(n,)`` int32)."""
    rows = logits[:, -1]
    nan = jnp.isnan(rows)
    # the TPU's argmax need not return the first of several NaNs
    v = rows.shape[-1]
    first_nan = jnp.min(jnp.where(nan, jnp.arange(v), v), axis=-1)
    tok = jnp.where(nan.any(axis=-1), first_nan, jnp.argmax(rows, axis=-1))
    finite = jnp.isfinite(rows).reshape(rows.shape[0], -1).all(axis=1)
    return tok.astype(jnp.int32), finite, rows[fetch]


class _Row:
    """One batch row of a step: ``token``, its argmax, and ``finite``,
    whether its logits are all finite, both computed on the device.
    ``np.asarray`` gives the logits themselves, ``logits(i)``: the step's
    fetch shares them among its rows."""

    def __init__(self, token: np.ndarray, finite: bool,
                 logits: Callable[[int], np.ndarray], i: int):
        self.token = token
        self.finite = finite
        self._logits = logits
        self._i = i

    def __array__(self, dtype=None, copy=None):
        return np.array(self._logits(self._i), dtype=dtype, copy=copy)


def default_serving_mesh() -> Mesh:
    """1x1 ("data", "model") mesh: the engine always runs through the
    sharded step builders; a single-device mesh makes every constraint a
    no-op without a separate unsharded code path."""
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))


@dataclass
class _StagingPrefill:
    """One in-flight chunked prefill: the queue head being committed,
    chunk by chunk, into a B=1 staging cache of ``bucket`` extent. The
    entry's ``prefill_offset`` tracks progress; ``rows`` holds the final
    chunk's logit row once complete (the first sampled token's source).
    ``match`` is the prefix-cache plan when the prompt hit (its pages stay
    pinned in the pool until admission or preemption); the staging cache
    was then seeded and progress starts at ``match.resume``."""
    entry: SlotEntry
    bucket: int
    step: Any                    # the cached (bucket, chunk) jitted step
    cache: Any                   # B=1 staging cache, threaded through chunks
    rows: _Row | None = None
    match: PrefixMatch | None = None

    @property
    def done(self) -> bool:
        return self.entry.prefill_offset >= self.entry.request.prompt_len


class Engine:
    """Slot-pool serving engine over one bound model.

    ``capacity`` is the decode batch (slot count); ``max_seq`` bounds
    ``prompt + max_new`` per request. ``paged=True`` (the default) backs the
    pool with shared pages of ``block`` tokens under a total budget of
    ``n_blocks`` pages (default ``capacity · ceil(max_seq / block)``, i.e.
    no oversubscription); a tighter budget admits mixed-length traffic the
    contiguous pool cannot hold, trading occasional preemption.
    ``paged=False`` keeps the PR 3 contiguous stripe pool (the memory A/B).
    ``continuous=False`` degrades to static batching: a gang of requests is
    admitted only into an *empty* pool and the next gang waits until every
    member finished — the every-request-waits-for-the-slowest behaviour
    continuous batching removes.

    ``prefill_mode`` selects chunked (default) or one-shot admission;
    ``chunk`` is the prefill chunk length (rounded up to a
    ``cfg.ssm_chunk`` multiple for the ssm/hybrid families so SSD chunk
    boundaries align); ``prefill_budget`` caps prefill tokens per engine
    step (default: one chunk).

    ``prefix_cache=True`` (the default) shares block-aligned prompt
    prefixes across requests through a token-hash radix tree over the
    paged pool (DESIGN.md §12) — active only where it is exact: paged
    layout, chunked prefill, a family in ``PREFIX_FAMILIES`` (the others
    keep recurrent state outside the page pool). ``prefix_hash_seed`` keys
    the block hash; streams are invariant to it.
    """

    def __init__(self, cfg, params, *, capacity: int = 4, max_seq: int = 256,
                 mesh: Mesh | None = None, continuous: bool = True,
                 paged: bool = True, block: int = 64,
                 n_blocks: int | None = None, fused: bool = True,
                 prefill_mode: str = "chunked", chunk: int = 16,
                 prefill_budget: int | None = None,
                 prefix_cache: bool = True, prefix_hash_seed: int = 0,
                 speculate_k: int | None = None,
                 draft_bits: int | None = None):
        cfg.validate()
        if prefill_mode not in ("chunked", "oneshot"):
            raise ConfigError(f"unknown prefill_mode {prefill_mode!r}")
        self.cfg = cfg
        self.capacity = capacity
        self.max_seq = max_seq
        self.continuous = continuous
        self.paged = paged
        self.fused = fused and paged
        self.prefill_mode = prefill_mode
        self.speculate_k = (cfg.speculate_k if speculate_k is None
                            else speculate_k)
        self.draft_bits = cfg.draft_bits if draft_bits is None else draft_bits
        if self.speculate_k:
            # DESIGN.md §14 gating: the draft's scratch K/V and the verify
            # window's rollback both live in the paged pool, and only the
            # attention families have state that *can* rewind (recurrent
            # ssm/hybrid state advances irreversibly); codebook heads
            # (musicgen) would need per-codebook acceptance.
            if not paged:
                raise ConfigError(
                    "speculative decoding requires the paged layout "
                    "(rollback rewinds page cells)")
            if cfg.family in ("ssm", "hybrid") or cfg.n_codebooks:
                raise ConfigError(
                    f"speculative decoding needs a transformer family "
                    f"without codebooks (recurrent state cannot roll back), "
                    f"got family={cfg.family!r} "
                    f"n_codebooks={cfg.n_codebooks}")
            from repro.kernels.sc_attention import sc_attention_bits_ok
            if not sc_attention_bits_ok(self.draft_bits):
                raise ConfigError(
                    f"speculative draft needs 2 <= draft_bits <= 8, "
                    f"got {self.draft_bits}")
        if cfg.family in ("ssm", "hybrid"):
            chunk = -(-chunk // cfg.ssm_chunk) * cfg.ssm_chunk
        self.chunk = chunk
        self.prefill_budget = chunk if prefill_budget is None else prefill_budget
        self.buckets = prompt_buckets(max_seq, chunk)
        self.mesh = mesh if mesh is not None else default_serving_mesh()
        self._m = bind(cfg)
        self.prefix: PrefixCache | None = None

        if paged:
            # one derivation (PagedSlotPool.plan) shapes both the compiled
            # step and the pool's host bookkeeping — they must never diverge.
            # fused=True (default) decodes straight on the page pool
            # (DESIGN.md §9, attention through the block table); fused=False
            # keeps the gather→decode→commit round-trip as the memory A/B.
            block, max_blocks, n_blocks = PagedSlotPool.plan(
                capacity, max_seq, block, n_blocks)
            self._decode, shardings, _ = cached_paged_decode_step(
                cfg, self.mesh, capacity=capacity, block=block,
                n_blocks=n_blocks, max_blocks=max_blocks, fused=self.fused)
            self._params = jax.device_put(params, shardings["params"])
            if self.speculate_k:
                # self-speculation (DESIGN.md §14): the draft model is the
                # *same weights* with the SC numeric forced on at the draft
                # width — the paper's multiplier as the cheap proposer. One
                # draft executable (k fused sub-steps), one exact verify
                # window (k + 1 rows), one rollback, all per pool shape.
                import dataclasses
                draft_cfg = dataclasses.replace(
                    cfg, use_sc_gemm=True, attn_sc=True,
                    sc_bits=self.draft_bits).validate()
                self.draft_cfg = draft_cfg
                self._draft, _, _ = cached_draft_loop_step(
                    draft_cfg, self.mesh, capacity=capacity, block=block,
                    n_blocks=n_blocks, max_blocks=max_blocks,
                    k=self.speculate_k)
                self._verify, _, _ = cached_verify_window_step(
                    cfg, self.mesh, capacity=capacity, block=block,
                    n_blocks=n_blocks, max_blocks=max_blocks,
                    width=self.speculate_k + 1)
                self._rollback, _, _ = cached_rollback_step(
                    cfg, self.mesh, capacity=capacity, block=block,
                    n_blocks=n_blocks, max_blocks=max_blocks,
                    width=self.speculate_k + 1)
            data = jax.device_put(
                cache_ops.paged_init(self._m.init_cache, capacity, n_blocks,
                                     block),
                shardings["cache"])
            self.pool: Any = PagedSlotPool(self._m, capacity, max_seq,
                                           block=block, n_blocks=n_blocks,
                                           cache=data)
            if (prefix_cache and prefill_mode == "chunked"
                    and cfg.family in PREFIX_FAMILIES):
                self.prefix = PrefixCache(block=self.pool.block,
                                          seed=prefix_hash_seed,
                                          align=self.chunk)
                self.pool.prefix = self.prefix
                self.pool.warm_prefix_edits()
        else:
            self._decode, shardings, _ = cached_decode_step(
                cfg, self.mesh, batch_size=capacity, seq_len=max_seq)
            self._params = jax.device_put(params, shardings["params"])
            pool_cache = jax.device_put(
                self._m.init_cache(capacity, max_seq), shardings["cache"])
            self.pool = SlotPool(self._m, capacity, max_seq, cache=pool_cache)

        tok_shape = ((capacity, 1, cfg.n_codebooks) if cfg.n_codebooks
                     else (capacity, 1))
        self._tok_buf = np.zeros(tok_shape, np.int32)
        self.queue = RequestQueue()
        self.stats: dict[str, Any] = {}
        self._step = 0          # decode-step counter (admissions are free)
        # cumulative totals, see counters(); the decode steps are _step
        self._counts = dict.fromkeys(_COUNTS, 0)
        self._admit_counter = 0
        self._staging: _StagingPrefill | None = None
        self._results: dict[str, RequestResult] = {}
        self._callbacks: dict[str, TokenCallback] = {}
        self._first_token_at: dict[str, float] = {}
        self._prefill_shapes: set[tuple[int, int]] = set()
        self._backpressure: dict[str, list[dict]] = {"admission": [],
                                                     "decode": []}

    # ------------------------------------------------------------ plumbing

    def counters(self) -> dict:
        """Cumulative totals since construction, as plain numbers; the
        difference of two snapshots taken between steps covers the steps in
        between.

        ``steps`` (:meth:`step` calls that had work), ``decode_steps`` (a
        speculative round counts as one) and ``decode_rows`` (live slots
        summed over them); ``prefill_chunks`` and ``prefill_rows`` (valid
        prompt tokens they computed); ``prefills`` and ``prompt_tokens``
        (requests admitted and their prompt lengths); ``prefix_hits``,
        ``prefix_misses`` and ``prefill_tokens_saved`` (prompt tokens a
        prefix hit seeded instead of computing); ``preemptions``,
        ``cow_copies``, ``prefix_reclaims`` and ``nonfinite_logit_rows``;
        ``device_sampled_rows`` (tokens taken from the device's greedy
        pick: a greedy request's every decode row and admission) and
        ``logit_rows_fetched`` (logit rows copied to the host: a step's
        ``temperature > 0`` rows, padded to a power of two, and all of a
        step's rows where a caller reads a greedy row's logits);
        ``queue_pops`` and ``queue_wait_s`` (seconds from ``submit`` or a
        preemption's requeue to the pop that starts the request's prefill,
        summed); the speculative ``spec_rounds``,
        ``spec_drafted_tokens``, ``spec_accepted_tokens`` and
        ``spec_emitted_tokens``; and for a mixture of experts
        ``moe_routed_rows`` ((token, expert) rows the routed experts
        computed, padding and free slots included) and
        ``moe_max_expert_rows`` (the busiest expert's rows, summed over MoE
        layer executions; with the first, the routing imbalance). Those two
        are kept on the device by the step programs (the pool's
        ``counts``) and fetched here alone; a prefill's rows count once it
        is admitted."""
        routed = busiest = 0
        counts = getattr(self.pool.cache, "counts", None)
        if counts is not None:
            with span("engine.counters"):
                routed, busiest = (int(c) for c in jax.device_get(counts))
        return {**self._counts, "decode_steps": self._step,
                "cow_copies": getattr(self.pool, "n_cow", 0),
                "prefix_reclaims": getattr(self.pool, "n_reclaimed", 0),
                "queue_pops": self.queue.pops,
                "queue_wait_s": self.queue.wait_s,
                "moe_routed_rows": routed, "moe_max_expert_rows": busiest}

    @property
    def has_work(self) -> bool:
        """Anything queued, staging, or live in a slot."""
        return (bool(self.queue) or bool(self.pool.entries)
                or self._staging is not None)

    def _check_request(self, req: Request) -> None:
        """Fail-fast request admission checks: capacity fit, and — under
        speculation — greedy sampling only, since the acceptance rule
        compares exact argmax against draft argmax (DESIGN.md §14); a
        sampled stream has no per-token right answer to accept against."""
        self.pool.check_fits(req)
        if self.speculate_k and req.temperature > 0:
            raise ConfigError(
                f"request {req.uid!r}: speculative decoding accepts greedy "
                f"(temperature == 0) requests only, got "
                f"temperature={req.temperature}")

    def _prefill_request(self, req: Request):
        """One-shot B=1 prefill through the cached sharded step for this
        prompt length; returns (last-token logit rows, single cache)."""
        prefill, shardings, _ = cached_prefill_step(
            self.cfg, self.mesh, batch_size=1, seq_len=req.prompt_len)
        self._prefill_shapes.add((req.prompt_len, 0))
        batch = {"tokens": jnp.asarray(req.prompt)[None]}
        logits, cache = prefill(self._params, batch)
        rows = self._rows(logits, [0] if req.temperature > 0 else [])
        return rows[0], cache

    def _rows(self, logits, sampled: Sequence[int]) -> list[_Row]:
        """The rows of a step's ``(B, 1, ..., V)`` logits, for
        :meth:`_sample`, from one ``device_get``: every row's greedy token
        and finite flag, reduced on the device, and the logits of the rows
        ``sampled`` names (those of ``temperature > 0`` requests), padded to
        a power of two so that the fetch compiles for few shapes. Any other
        row's logits are fetched, with the whole step's, only if asked."""
        b, n = logits.shape[0], len(sampled)
        pad = min(1 << (n - 1).bit_length(), b) if n else 0
        idx = np.asarray(list(sampled) + list(sampled[-1:]) * (pad - n),
                         np.int32)
        out = _reduce_rows(logits, idx)
        with span("engine.sync"):
            tok, finite, got = jax.device_get(out)
        self._counts["logit_rows_fetched"] += pad
        host = dict(zip(sampled, got))

        def row(i: int) -> np.ndarray:
            if i not in host:
                self._counts["logit_rows_fetched"] += b
                with span("engine.sync"):
                    host.update(enumerate(
                        np.asarray(jax.device_get(logits[:, -1]))))
            return host[i]
        return [_Row(tok[i], bool(finite[i]), row, i) for i in range(b)]

    def _sample(self, entry: SlotEntry, row: _Row) -> np.ndarray:
        """One token from a logit row ((V,) or (K, V) for codebooks).

        Greedy takes the row's argmax, picked on the device. temperature > 0
        takes the row's logits, fetched with the step's tokens, and walks a
        per-request PRNG chain (seeded by the request, split once per
        emitted token), so a stream is a function of the request alone —
        which slot or engine step produced it is irrelevant (and a
        preempted, restarted request regenerates the identical stream). A
        row holding NaN or inf is counted (``stats["nonfinite_logit_rows"]``):
        argmax over it still returns an id, so without the count the fault
        would be silent.
        """
        if not row.finite:
            self._counts["nonfinite_logit_rows"] += 1
        req = entry.request
        if req.temperature <= 0:
            self._counts["device_sampled_rows"] += 1
            return row.token
        if entry.key is None:
            entry.key = jax.random.PRNGKey(req.seed)
        entry.key, sub = jax.random.split(entry.key)
        tok = jax.random.categorical(
            sub, jnp.asarray(np.asarray(row)) / req.temperature, axis=-1)
        return np.asarray(tok, np.int32)

    def _finish_reason(self, entry: SlotEntry, tok: np.ndarray) -> str | None:
        req = entry.request
        if (req.eos_id is not None and tok.ndim == 0
                and int(tok) == req.eos_id):
            return "eos"
        if entry.n_generated >= req.max_new_tokens:
            return "length"
        return None

    def _emit(self, slot: int, entry: SlotEntry, tok: np.ndarray) -> None:
        """Record a sampled token, push it to the request's stream, and
        finish + evict or park it for the next decode step."""
        entry.generated.append(tok)
        uid = entry.request.uid
        self._first_token_at.setdefault(uid, time.perf_counter())
        reason = self._finish_reason(entry, tok)
        cb = self._callbacks.get(uid)
        if cb is not None:
            cb(uid, entry.n_generated - 1, tok, reason)
        if reason is not None:
            with span("engine.evict", uid=uid):
                self.pool.evict(slot)
            self._callbacks.pop(uid, None)
            req = entry.request
            self._results[uid] = RequestResult(
                uid=uid,
                tokens=np.stack(entry.generated).astype(np.int32),
                prompt_len=req.prompt_len,
                finished_reason=reason,
                enqueued_at=req.enqueued_at,
                admitted_at=entry.admitted_at,
                finished_at=time.perf_counter(),
                admit_step=entry.admit_step,
                finish_step=self._step,
                first_token_at=self._first_token_at.pop(uid),
            )
        else:
            self._tok_buf[slot] = tok

    # ----------------------------------------------------- chunked prefill

    def _start_prefill(self, req: Request) -> _StagingPrefill:
        """Pop the queue head into a fresh staging prefill: pick its bucket,
        build (or reuse) the (bucket, chunk) executable, and zero-init the
        staging cache. The entry is created *now* — its ``admit_index``
        makes the staging prefill the youngest admission for preemption
        ordering, and ``prefill_offset`` tracks chunk progress.

        With a prefix cache, the prompt is first matched against the radix
        tree: on a hit the matched pages are pinned (so the LRU reclaimer
        cannot surrender them mid-staging), the staging cache is seeded
        with their K/V rows, and chunk progress starts at the resume
        offset — the shared span is never recomputed."""
        self.pool.check_fits(req)
        bucket = bucket_for(req.prompt_len, self.buckets)
        step, shardings, _ = cached_chunked_prefill_step(
            self.cfg, self.mesh, seq_len=bucket, chunk=self.chunk)
        self._prefill_shapes.add((bucket, self.chunk))
        cache = jax.device_put(self._m.init_cache(1, bucket),
                               shardings["cache"])
        entry = SlotEntry(request=req, admitted_at=0.0, admit_step=self._step,
                          admit_index=self._admit_counter)
        self._admit_counter += 1
        match = None
        if self.prefix is not None:
            with span("engine.prefix_match", uid=req.uid):
                plan = self.prefix.match(req.prompt)
                if plan.hit:
                    match = plan
                    self.pool.pin_pages(plan.pages)
                    cache = cache_ops.prefix_seed(
                        cache, self.pool.cache, plan.pages,
                        block=self.pool.block, resume=plan.resume)
                    entry.prefill_offset = plan.resume
                    self._counts["prefix_hits"] += 1
                else:
                    self._counts["prefix_misses"] += 1
        return _StagingPrefill(entry=entry, bucket=bucket, step=step,
                               cache=cache, match=match)

    def _prefill_chunk_once(self, st: _StagingPrefill) -> None:
        """Commit one chunk of the staging prompt (the final chunk is
        zero-padded past ``n_valid`` real tokens)."""
        req = st.entry.request
        off = st.entry.prefill_offset
        nv = min(self.chunk, req.prompt_len - off)
        toks = np.zeros((self.chunk,) + req.prompt.shape[1:], np.int32)
        toks[:nv] = req.prompt[off:off + nv]
        batch = {"tokens": jnp.asarray(toks)[None],
                 "n_valid": jnp.asarray([nv], jnp.int32)}
        logits, st.cache = st.step(self._params, st.cache, batch)
        st.entry.prefill_offset = off + nv
        self._counts["prefill_chunks"] += 1
        self._counts["prefill_rows"] += nv
        if st.done:
            st.rows = self._rows(logits,
                                 [0] if req.temperature > 0 else [])[0]

    def _can_admit_staged(self, st: _StagingPrefill) -> bool:
        if not self.pool.has_free:
            return False
        if not self.paged:
            return True
        return self.pool.can_admit(st.entry.request, match=st.match)

    def _admit_staged(self) -> None:
        """Completed staging prefill → pool admission: truncate the bucket
        padding to the exact prompt extent and insert through the same
        ``slot_insert``/``paged_insert`` path a one-shot prefill takes (so
        page accounting sees the prompt, never the bucket), then sample and
        emit the first token from the held final-chunk logits. A prefix
        hit admits through ``admit_prefix`` instead (attach + CoW), the
        CoW source's staging pin is released, and either way the prompt's
        full pages are registered in the radix tree for future hits."""
        st = self._staging
        self._staging = None
        req = st.entry.request
        with span("engine.admit", uid=req.uid):
            # the paged pool takes the bucket-padded staging cache and the
            # prompt length (one admission program per bucket); the
            # contiguous pool takes the exact extent
            single = (st.cache if self.paged else
                      cache_ops.truncate_seq(st.cache, req.prompt_len))
            st.entry.admitted_at = time.perf_counter()
            st.entry.admit_step = self._step
            if st.match is not None:
                slot = self.pool.admit_prefix(st.entry, single, st.match)
                if st.match.cow_src is not None:
                    self.pool.unpin_pages([st.match.cow_src])
                # count the skipped span at admission, not staging start: a
                # preempted staging prefill re-stages (and re-matches), so
                # an early count would tally the same request's resume twice
                self._counts["prefill_tokens_saved"] += st.match.resume
            else:
                slot = self.pool.admit(st.entry, single)
            if self.prefix is not None:
                full = req.prompt_len // self.pool.block
                new = self.prefix.insert(
                    req.prompt, self.pool.tables[slot, :full].tolist())
                self.pool.retain_pages(new)
            self._counts["prefills"] += 1
            self._counts["prompt_tokens"] += req.prompt_len
            self._emit(slot, st.entry, self._sample(st.entry, st.rows))

    def _advance_prefill(self, budget_tokens: int) -> None:
        """Spend up to ``budget_tokens`` of prefill-chunk work: advance the
        in-flight staging prompt (starting the queue head if idle) and
        admit it the moment it completes and a slot + pages are free. A
        completed-but-unadmittable prompt is *held* in staging — the live
        slots keep decoding and free pages as they finish."""
        chunks_left = max(1, budget_tokens // self.chunk)
        while True:
            if self._staging is None:
                if not self.queue:
                    return
                self._staging = self._start_prefill(self.queue.pop())
            st = self._staging
            while not st.done and chunks_left > 0:
                with span("engine.prefill_chunk", uid=st.entry.request.uid):
                    self._prefill_chunk_once(st)
                chunks_left -= 1
            if not st.done:
                return                       # budget exhausted mid-prompt
            if not self._can_admit_staged(st):
                self._note_backpressure("admission", st.entry.request.uid)
                return                       # hold until slots/pages free
            self._admit_staged()
            if chunks_left <= 0:
                return

    # --------------------------------------------------- one-shot admission

    def _may_admit_next(self) -> bool:
        """Paged backpressure at admission: hold the queue head back until
        its prompt's pages fit — it stays queued (not failed) and the live
        slots keep decoding, freeing pages as they finish."""
        if not self.paged:
            return True
        return self.pool.can_admit(self.queue.peek())

    def _admit_one(self, req: Request) -> None:
        with span("engine.admit", uid=req.uid):
            rows, single_cache = self._prefill_request(req)
            entry = SlotEntry(request=req, admitted_at=time.perf_counter(),
                              admit_step=self._step,
                              admit_index=self._admit_counter,
                              prefill_offset=req.prompt_len)
            self._admit_counter += 1
            self._counts["prefills"] += 1
            self._counts["prefill_rows"] += req.prompt_len
            self._counts["prompt_tokens"] += req.prompt_len
            slot = self.pool.admit(entry, single_cache)
            self._emit(slot, entry, self._sample(entry, rows))

    # ----------------------------------------------------------- the pool

    def _preempt_youngest(self) -> None:
        """Evict the most recently admitted slot — or drop the in-flight
        staging prefill if it is younger — and re-queue its request
        (progress is discarded; determinism makes the regenerated stream
        identical). Youngest-first keeps FCFS intact: the oldest live
        request always advances, so the loop always makes progress."""
        cands: list[tuple[int, int | None]] = [
            (e.admit_index, s) for s, e in self.pool.entries.items()]
        if self._staging is not None:
            cands.append((self._staging.entry.admit_index, None))
        _, victim = max(cands, key=lambda t: t[0])
        entry = (self._staging.entry if victim is None
                 else self.pool.entries[victim])
        with span("engine.preempt", uid=entry.request.uid):
            if victim is None:
                st = self._staging
                self._staging = None
                if st.match is not None:    # release the staging pins
                    self.pool.unpin_pages(st.match.pages)
            else:
                self.pool.evict(victim)
            self.queue.requeue(entry.request)
        self._counts["preemptions"] += 1

    def _note_backpressure(self, reason: str, uid: str | None,
                           pages_needed: int | None = None,
                           pages_free: int | None = None) -> None:
        """Record a backpressure event for ``run()`` stats; consecutive
        holds of the same request collapse to one event."""
        events = self._backpressure[reason]
        if events and events[-1]["uid"] == uid:
            return
        if pages_free is None and self.paged:
            pages_free = self.pool.free_pages
        events.append({"uid": uid, "pages_needed": pages_needed,
                       "pages_free": pages_free})

    def _grow_pages(self, width: int = 1) -> None:
        """Allocate (and make writable) each live slot's next ``width``
        write positions' pages, preempting under pressure. Slots are grown
        oldest-first so preemption (youngest first) never starves the head
        of the line. ``width > 1`` is the speculative window (DESIGN.md
        §14): only positions a slot can still *keep* are ensured —
        ``min(width, remaining)`` — the window's overshoot past a request's
        budget lands in unallocated entries (→ trash block) and is zeroed
        by rollback. The oldest slot alone always fits: its ensured span
        ends at most at ``prompt + max_new - 1 ≤ max_seq - 1``, the
        ``check_fits`` bound."""
        with span("engine.grow_pages"):
            for slot in sorted(self.pool.entries,
                               key=lambda s: self.pool.entries[s].admit_index):
                while slot in self.pool.entries:
                    entry = self.pool.entries[slot]
                    n_keep = min(width, entry.request.max_new_tokens
                                 - entry.n_generated)
                    base = entry.next_write_pos
                    try:
                        for i in range(n_keep):
                            self.pool.ensure_page(slot, base + i)
                        break
                    except PoolExhausted as e:
                        self._note_backpressure(e.reason, e.uid,
                                                e.pages_needed, e.pages_free)
                        if (len(self.pool.entries) <= 1
                                and self._staging is None):
                            raise   # run() pre-check makes this unreachable
                        self._preempt_youngest()

    def _decode_once(self) -> list[_Row]:
        """One batched decode step over every slot; returns its C rows."""
        if self.paged:
            self._grow_pages()
        with span("engine.decode"):
            batch = {"tokens": jnp.asarray(self._tok_buf)}
            if self.paged:
                logits, self.pool.cache = self._decode(
                    self._params, self.pool.cache,
                    jnp.asarray(self.pool.tables), batch)
            else:
                logits, self.pool.cache = self._decode(
                    self._params, self.pool.cache, batch)
        self._step += 1
        self._counts["decode_rows"] += len(self.pool.entries)
        return self._rows(logits, [
            slot for slot, e in sorted(self.pool.entries.items())
            if e.request.temperature > 0])

    def _speculate_once(self) -> None:
        """One draft → verify → rollback round (DESIGN.md §14), emitting
        1..k+1 exact tokens per live slot.

        Protocol, per slot at write position ``p`` (last sampled token τ in
        ``_tok_buf``, its K/V not yet written):

        1. *Draft*: k fused SC-numeric decode sub-steps propose
           ``d_1..d_k`` (greedy chain from τ), writing scratch K/V at
           ``[p, p + k)``; the returned pool's positions are restored to
           ``p``.
        2. *Verify*: one exact (k+1)-row window over ``[τ, d_1..d_k]``
           rewrites ``[p, p + k]`` with exact K/V (the window scatter fully
           overwrites the draft scratch before any attention read, so
           verification never sees draft numerics), commits all rows to
           pages, and returns the per-row exact argmax ``e_0..e_k``.
        3. *Accept* (host): j = longest prefix with ``e_i == d_{i+1}``;
           emit ``e_0..e_j`` — j accepted draft tokens plus one exact
           token that is the correction on first mismatch or the free
           bonus row when all k matched — capped at the request's
           remaining budget.
        4. *Rollback* (device, **before** any eviction mutates the pool):
           positions rewind to ``p + accepted`` and rejected cells are
           zeroed. Free slots roll back their whole window (their writes
           landed in the trash block), leaving zero net position drift.

        Bit-identity is by construction: every emitted token is an *exact*
        argmax over the same prefix the sequential baseline conditions on —
        the draft only chooses how many exact tokens one round yields.
        """
        k = self.speculate_k
        width = k + 1
        self._grow_pages(width)
        if not self.pool.entries:
            return      # the window's growth preempted every slot but one,
                        # then that one finished? unreachable, but be safe
        tables = jnp.asarray(self.pool.tables)
        with span("engine.draft"):
            draft_toks, self.pool.cache = self._draft(
                self._params, self.pool.cache, tables,
                {"tokens": jnp.asarray(self._tok_buf)})
        with span("engine.sync"):
            draft_host = np.asarray(jax.device_get(draft_toks))  # (C, k)
        window = np.concatenate([self._tok_buf, draft_host], axis=1)
        with span("engine.verify"):
            exact_toks, self.pool.cache = self._verify(
                self._params, self.pool.cache, tables,
                {"tokens": jnp.asarray(window)})
        with span("engine.sync"):
            exact_host = np.asarray(jax.device_get(exact_toks))  # (C, k+1)
        self._step += 1
        self._counts["spec_rounds"] += 1
        self._counts["decode_rows"] += len(self.pool.entries)

        accept = np.zeros((self.capacity,), np.int32)
        emit_n: dict[int, int] = {}
        for slot, entry in self.pool.entries.items():
            j = 0
            while j < k and exact_host[slot, j] == draft_host[slot, j]:
                j += 1
            remaining = entry.request.max_new_tokens - entry.n_generated
            t = min(j + 1, remaining)
            accept[slot] = t
            emit_n[slot] = t
            self._counts["spec_drafted_tokens"] += k
            self._counts["spec_accepted_tokens"] += min(j, t)
            self._counts["spec_emitted_tokens"] += t
        # rollback BEFORE the emission loop: eviction (eos/length finish)
        # resets a slot's positions and pages itself, and running it first
        # would leave rollback rewinding a slot the pool already recycled
        with span("engine.rollback"):
            self.pool.cache = self._rollback(self.pool.cache, tables,
                                             jnp.asarray(accept))
        with span("engine.sample"):
            for slot in self.pool.active_slots:
                entry = self.pool.entries[slot]
                for i in range(emit_n[slot]):
                    self._emit(slot, entry, exact_host[slot, i])
                    if slot not in self.pool.entries:
                        break   # finished (eos/length): drop the tail —
                                # eviction already rewound its positions

    # ------------------------------------------------------ the scheduler

    def step(self) -> bool:
        """One scheduler step: ≤ ``prefill_budget`` tokens of prefill-chunk
        work (admitting completed prompts), then one batched decode over
        the live slots, emitting every sampled token through the streaming
        surface. Returns whether work remains."""
        if not self.has_work:
            return False
        self._counts["steps"] += 1
        with span("engine.step"):
            self._schedule()
        return self.has_work

    def _schedule(self) -> None:
        """The body of :meth:`step`: prompt work, then the batched decode
        and the emission of its tokens."""
        with span("engine.prefill"):
            if self.prefill_mode == "chunked":
                if self.continuous:
                    self._advance_prefill(self.prefill_budget)
                elif not self.pool.entries:
                    # static gang admission: fill the empty pool
                    # back-to-back (the admission stall is the A/B point of
                    # static mode)
                    self._advance_prefill(self.max_seq * self.capacity)
            else:
                may_admit = self.continuous or not self.pool.entries
                while may_admit and self.pool.has_free and self.queue \
                        and self._may_admit_next():
                    self._admit_one(self.queue.pop())
                    if not self.continuous and not self.pool.has_free:
                        break
        if not self.pool.entries:
            st = self._staging
            if (st is not None and st.done and st.match is not None
                    and not self._can_admit_staged(st)):
                # the sharing plan itself can be what pins too much
                # capacity (warm pages + the CoW source are off the free
                # list while staged): drop it — the staging cache is
                # complete, the seeded span bit-identical to a computed
                # one — and admit privately like a miss before declaring
                # the request unservable. The skipped span still counts as
                # saved: it was never recomputed.
                self.pool.unpin_pages(st.match.pages)
                self._counts["prefill_tokens_saved"] += st.match.resume
                st.match = None
                if self._can_admit_staged(st):
                    self._admit_staged()
        if not self.pool.entries:
            # an empty pool has every slot and page free (or reclaimable),
            # so anything still refused now can never be admitted (it
            # bypassed the run() pre-check via queue.submit) — fail, don't
            # spin
            st = self._staging
            if st is not None and st.done and not self._can_admit_staged(st):
                self._staging = None
                raise PoolExhausted(
                    f"request {st.entry.request.uid!r} cannot be admitted "
                    f"even into an empty pool "
                    f"(n_blocks={getattr(self.pool, 'n_blocks', None)})",
                    uid=st.entry.request.uid)
            if (self.prefill_mode == "oneshot" and self.queue
                    and not self._may_admit_next()):
                raise PoolExhausted(
                    f"request {self.queue.peek().uid!r} cannot be admitted "
                    f"even into an empty pool "
                    f"(n_blocks={getattr(self.pool, 'n_blocks', None)})",
                    uid=self.queue.peek().uid)
            return                  # mid-prefill, or gang finished at admit
        if self.speculate_k:
            self._speculate_once()
            return
        rows = self._decode_once()
        with span("engine.sample"):
            for slot in self.pool.active_slots:
                entry = self.pool.entries[slot]
                self._emit(slot, entry, self._sample(entry, rows[slot]))

    # ------------------------------------------------- streaming surface

    def submit(self, request: Request,
               on_token: TokenCallback | None = None) -> None:
        """Queue a request; optional ``on_token`` receives every emitted
        token (including post-preemption replays) as decode steps land.
        Unfittable requests are refused here, before any device work."""
        self._check_request(request)
        self.queue.submit(request)
        if on_token is not None:
            self._callbacks[request.uid] = on_token

    def stream(self, request: Request) -> Iterator[np.ndarray]:
        """Submit ``request`` and yield its tokens as they are generated,
        driving the engine (pull-based): each ``next()`` runs scheduler
        steps until the next token lands. Co-batched requests keep
        advancing — their results collect for a later ``run()`` — and a
        preempted-and-readmitted stream replays bit-identically (replayed
        indexes are deduped, so consumers see each token exactly once)."""
        buf: list[tuple[int, np.ndarray]] = []
        done: list[str] = []

        def on_token(uid, index, tok, reason):
            buf.append((index, tok))
            if reason is not None:
                done.append(reason)

        self.submit(request, on_token=on_token)
        nxt = 0
        while True:
            while buf:
                index, tok = buf.pop(0)
                if index == nxt:        # index < nxt: preemption replay
                    nxt += 1
                    yield tok
            if done:
                # the generator IS this request's result surface — drop the
                # collected RequestResult so a later run() doesn't resurface it
                self._results.pop(request.uid, None)
                return
            self.step()
            if not self.has_work and not buf and not done:
                raise EngineInvariantError(
                    f"engine drained without finishing {request.uid!r}")

    # ----------------------------------------------------------- the loop

    def run(self, requests: Sequence[Request] = ()) -> list[RequestResult]:
        """Drain ``requests`` (plus anything already queued); returns
        results in submission order. Populates ``self.stats``."""
        # fail fast on requests that can *never* fit, before any device
        # work — a mid-run refusal at admission would abort the loop and
        # discard every already-finished stream (the pools stay the
        # backstop). Transient shortage is not failure: paged admission
        # waits for pages, decode-time exhaustion preempts and re-queues.
        for r in requests:
            self._check_request(r)
        order = [r.uid for r in requests]
        for r in requests:
            self.queue.submit(r)
        t0 = time.perf_counter()
        c0 = self.counters()
        self._backpressure = {"admission": [], "decode": []}

        while self.step():
            pass

        wall = time.perf_counter() - t0
        c1 = self.counters()
        d = {k: c1[k] - c0[k] for k in c1}
        if order:
            out = [self._results.pop(uid) for uid in order]
        else:
            out = sorted(self._results.values(), key=lambda r: r.admitted_at)
            self._results.clear()
        generated = sum(r.n_generated for r in out)

        def pctl(values, q):
            v = sorted(values) or [0.0]
            if q == 0.5:
                return v[len(v) // 2]
            return v[min(len(v) - 1, int(np.ceil(q * len(v))) - 1)]

        lats = [r.latency_s for r in out]
        ttfts = [r.ttft_s for r in out]
        itls = [r.itl_s for r in out if r.n_generated > 1]
        self.stats = {
            "mode": "continuous" if self.continuous else "static",
            "layout": "paged" if self.paged else "contiguous",
            "prefill_mode": self.prefill_mode,
            "requests": len(out),
            "generated_tokens": generated,
            **{k: d[k] for k in ("decode_steps", "prefills",
                                 "prefill_chunks", "preemptions",
                                 "nonfinite_logit_rows")},
            "wall_s": wall,
            "tok_per_s": generated / wall if wall > 0 else float("inf"),
            "p50_latency_s": pctl(lats, 0.5),
            "p99_latency_s": pctl(lats, 0.99),
            "ttft_p50_s": pctl(ttfts, 0.5),
            "ttft_p99_s": pctl(ttfts, 0.99),
            "itl_p50_s": pctl(itls, 0.5),
            "itl_p99_s": pctl(itls, 0.99),
            "chunk": self.chunk,
            "buckets": self.buckets,
            "prefill_executables": len(self._prefill_shapes),
        }
        if self.paged:
            self.stats.update({
                "block": self.pool.block,
                "n_blocks": self.pool.n_blocks,
                "pages_in_use": self.pool.pages_in_use,
                "pages_live": self.pool.pages_live,
                "peak_pages": self.pool.peak_pages,
                "decode_path": "fused" if self.fused else "gather",
                "backpressure": self._backpressure,
            })
        self.stats["speculative"] = bool(self.speculate_k)
        if self.speculate_k:
            rounds, drafted = d["spec_rounds"], d["spec_drafted_tokens"]
            accepted = d["spec_accepted_tokens"]
            self.stats.update({
                "speculate_k": self.speculate_k,
                "draft_bits": self.draft_bits,
                "spec_rounds": rounds,
                "spec_drafted_tokens": drafted,
                "spec_accepted_tokens": accepted,
                "spec_acceptance_rate": accepted / max(drafted, 1),
                "spec_tokens_per_round":
                    d["spec_emitted_tokens"] / max(rounds, 1),
            })
        self.stats["prefix_cache"] = self.prefix is not None
        if self.prefix is not None:
            hits, misses = d["prefix_hits"], d["prefix_misses"]
            self.stats.update({
                "prefix_hits": hits,
                "prefix_misses": misses,
                "prefix_hit_rate": hits / max(hits + misses, 1),
                **{k: d[k] for k in ("prefill_tokens_saved", "cow_copies",
                                     "prefix_reclaims")},
                "prefix_retained_pages": len(self.pool.retained),
            })
        return out
