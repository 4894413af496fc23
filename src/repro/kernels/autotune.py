"""Autotuner for the Pallas kernels: per-shape configuration sweeps with a
persistent on-disk cache, shared by all three kernel families.

Tuned subspaces (DESIGN.md §2.3, §6):

* SC-GEMM (:class:`KernelConfig`) — MXU tile sizes (bm, bn), the K-block bk
  held in VMEM, and the residual's lane-parallel chunk width.
* bit-parallel stream multiply (:class:`StreamConfig`) — rows-per-call group
  width of ``sc_bitops.sc_stream_mul_pallas`` (how many 128-lane rows each
  grid step processes, which also sets the flat-input padding group).
* flash attention (:class:`FlashConfig`) — (bq, bk) block sizes of
  ``kernels.flash_attention``.
* paged decode attention (:class:`PagedFlashConfig`) — KV heads per grid
  step of ``kernels.paged_attention`` (how much of the page pool's head
  axis one table-walk step loads into VMEM).

The best point varies with problem shape, backend, **and interpret mode** —
interpret-mode timings (Python-loop execution on CPU) say nothing about
compiled Mosaic throughput, so the cache key carries all three. Winners are
persisted as JSON once per key and served from the cache afterwards,
including across processes.

Entry points:

* :func:`get_or_tune` / :func:`get_or_tune_stream` / :func:`get_or_tune_flash`
  — cached lookup + sweep; used by the ``ops.py`` wrappers' ``tune=True``
  paths. Safe to reach from inside ``jax.jit`` tracing: a cache hit resolves
  from shape alone, and a miss sweeps *synthetic* operands of the same shape
  in a worker thread (JAX trace state is thread-local, so the sweep runs
  outside the caller's trace — timing traced abstract values is meaningless,
  and the sweep never touches the caller's tracers).
* :func:`choose_impl` — backend-level dispatch behind
  ``core.sc_matmul(..., impl="auto")``.
* :class:`AutotuneCache` — the JSON cache (default location
  ``$REPRO_AUTOTUNE_CACHE`` or ``~/.cache/repro/sc_gemm_autotune.json``).
"""
from __future__ import annotations

import dataclasses
import json
import os
import tempfile
import time
from dataclasses import asdict, dataclass
from pathlib import Path
from typing import Callable, Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "KernelConfig",
    "StreamConfig",
    "FlashConfig",
    "PagedFlashConfig",
    "AutotuneCache",
    "candidate_configs",
    "candidate_stream_configs",
    "candidate_flash_configs",
    "candidate_paged_configs",
    "autotune",
    "get_or_tune",
    "get_or_tune_stream",
    "get_or_tune_flash",
    "get_or_tune_paged",
    "choose_impl",
    "best_of_us",
    "default_cache_path",
    "bucket_m",
    "SKINNY_M_MAX",
]

CACHE_ENV = "REPRO_AUTOTUNE_CACHE"
#: v2 added the interpret flag to every key. v3 buckets skinny (decode-
#: shaped) M extents and widens their candidate grid with GEMV-like bm
#: tiles — a v2 winner at a skinny key was swept without those candidates,
#: so keeping it would permanently pin decode shapes to the old 128-row
#: tile (a cache hit never re-sweeps). v4 adds the paged-flash family
#: (``paged:`` keys) and bumps the document schema with it so every cache
#: file carries exactly one key grammar. v5 appends the SC-attention
#: variant segment (``:sc<bits>``) to the flash and paged key grammars —
#: a v4 winner was swept on the float contraction only and must not serve
#: the SC path (or vice versa). Older documents are *invalidated* on load
#: (not migrated); affected shapes simply re-tune once. v6 follows the
#: SC-GEMM kernel's move to a transposed LHS (M on lanes, K on sublanes),
#: which changed what every block configuration costs.
CACHE_VERSION = 6

#: VMEM budget used to prune candidates; conservative fraction of ~16 MiB.
VMEM_BUDGET_BYTES = 12 * 2 ** 20

#: Largest M treated as "skinny" (decode-shaped: one token per sequence, so
#: M = live batch). Skinny problems share a bucketed cache key and get
#: GEMV-like bm candidates — see :func:`bucket_m`.
SKINNY_M_MAX = 64


def bucket_m(m: int) -> int:
    """Bucket class for the M extent of a GEMM tuning key.

    Decode-time ``sc_dense`` calls are (B, 1, d)-shaped — M is the live
    batch, which fluctuates with serving load. Bucketing skinny M to the
    next power of two (8, 16, 32, 64) makes every decode batch size in a
    bucket resolve to one tuned GEMV-like config instead of sweeping (and
    caching) per exact batch size; prefill/train-sized M (> SKINNY_M_MAX)
    keeps its exact extent, where the tile choice genuinely depends on it.
    """
    if m > SKINNY_M_MAX:
        return m
    b = 8
    while b < m:
        b *= 2
    return b


def _is_tracer(x) -> bool:
    return isinstance(x, jax.core.Tracer)


@dataclass(frozen=True)
class KernelConfig:
    """One point in the SC-GEMM kernel's tuning space."""
    bm: int = 128
    bn: int = 128
    bk: int = 512
    chunk: int = 8

    def vmem_bytes(self) -> int:
        """Estimated VMEM working set of one grid step (DESIGN.md §2.2).

        The LHS planes enter transposed, ``(bk, bm)``, so ``bm`` lies on
        lanes and pads to a multiple of 128 there. Inputs and the output
        are double-buffered by the Pallas pipeline; the MXU term's loaded
        and cast operands are temporaries of the input tiles' size."""
        lanes_m = _round_up(self.bm, 128)
        rows_m = _round_up(self.bm, 8)
        lhs = 2 * 2 * self.bk * lanes_m          # sxᵀ, mxᵀ, double-buffered
        rhs = 2 * 2 * self.bk * self.bn          # sy, my, double-buffered
        out = 3 * rows_m * self.bn               # out (x2) + acc scratch
        temps = 2 * self.bk * (lanes_m + self.bn)
        return 4 * (lhs + rhs + out + temps)

    def is_valid(self) -> bool:
        return (self.bm % 8 == 0 and self.bn % 128 == 0 and
                self.bk % self.chunk == 0 and self.chunk > 0)


@dataclass(frozen=True)
class StreamConfig:
    """Tuning point for ``sc_bitops.sc_stream_mul_pallas``: how many 128-lane
    rows one grid step processes (= the flat-input padding group width)."""
    block_rows: int = 8

    def is_valid(self) -> bool:
        return self.block_rows > 0


@dataclass(frozen=True)
class PagedFlashConfig:
    """Tuning point for ``kernels.paged_attention``: how many KV heads one
    table-walk grid step processes. Larger ``kvh`` shrinks the grid (fewer
    page-walk passes over the table) but multiplies the per-step VMEM tiles
    and scratch; the best point depends on head count, head dim, and the
    page geometry, so it is swept like every other kernel subspace."""
    kvh: int = 1

    def vmem_bytes(self, *, max_blocks: int, block: int, g: int,
                   d: int) -> int:
        """Per-step working set: whole-row scratch plus the q/k/v/out tiles
        of one page step. Full-MHA (``g == 1``) swaps the score scratch for
        a raw K-page buffer of the same row extent (scored whole-row at the
        finish step; 4 bytes/elt is an upper bound — bf16 caches halve it)."""
        s_len = max_blocks * block
        scratch0 = (s_len * self.kvh * d if g == 1   # raw K buffer
                    else self.kvh * g * s_len)       # score scratch
        return 4 * (scratch0
                    + s_len * self.kvh * d        # fp32 V scratch
                    + 2 * self.kvh * g * d        # q + out tiles
                    + 2 * block * self.kvh * d)   # k + v tiles

    def is_valid(self) -> bool:
        return self.kvh > 0


@dataclass(frozen=True)
class FlashConfig:
    """Tuning point for ``kernels.flash_attention``: (bq, bk) block sizes."""
    bq: int = 256
    bk: int = 512

    def vmem_bytes(self, d: int = 256) -> int:
        """Working set for head dim ``d``: q + k + v + out + acc tiles plus
        the m/l lane scratch (callers pass the real head dim when pruning)."""
        return 4 * (2 * self.bq * d + 2 * self.bk * d + self.bq * d
                    + 2 * self.bq * 128)

    def is_valid(self) -> bool:
        return self.bq % 128 == 0 and self.bk % 128 == 0


def default_cache_path() -> Path:
    env = os.environ.get(CACHE_ENV)
    if env:
        return Path(env)
    base = Path(os.environ.get("XDG_CACHE_HOME", str(Path.home() / ".cache")))
    return base / "repro" / "sc_gemm_autotune.json"


def _mode(interpret: bool | None, backend: str) -> str:
    """Key-segment for the execution mode. An omitted ``interpret`` is
    inferred from the *key's* backend (not the live process), so inspecting
    or pre-seeding another backend's entries from a CPU process builds the
    keys that backend's processes actually use. Library call paths always
    pass the resolved flag (``ops.default_interpret`` has the same rule)."""
    if interpret is None:
        interpret = backend != "tpu"
    return "interp" if interpret else "compiled"


class AutotuneCache:
    """Persistent key -> config map, stored as one JSON document.

    Keys are built by the ``key*`` staticmethods and always carry the op
    family, backend, and interpret mode, so interpret-mode sweeps can never
    serve compiled runs (or vice versa) on the same machine.
    """

    def __init__(self, path: str | os.PathLike | None = None):
        self.path = Path(path) if path is not None else default_cache_path()
        self._entries: dict[str, dict] = {}
        self._load()

    @staticmethod
    def key(m: int, k: int, n: int, bits: int, backend: str | None = None,
            interpret: bool | None = None) -> str:
        """Skinny (decode-shaped) M extents are bucketed (:func:`bucket_m`),
        so every live-batch size in a bucket shares one tuned entry."""
        backend = backend or jax.default_backend()
        return (f"sc_gemm:{backend}:{_mode(interpret, backend)}"
                f":m{bucket_m(m)}:k{k}:n{n}:b{bits}")

    @staticmethod
    def stream_key(size: int, bits: int, backend: str | None = None,
                   interpret: bool | None = None) -> str:
        """``size`` is the flat element count (padding depends on the
        candidate's group width, so the key carries the unpadded size)."""
        backend = backend or jax.default_backend()
        return f"sc_stream:{backend}:{_mode(interpret, backend)}:s{size}:b{bits}"

    @staticmethod
    def flash_key(b: int, h: int, kv: int, sq: int, skv: int, d: int,
                  causal: bool, backend: str | None = None,
                  interpret: bool | None = None,
                  dtype: str = "float32",
                  sc_bits: int | None = None) -> str:
        """Unlike SC-GEMM (always quantized from fp32 inside the kernel
        call), flash operands keep their real dtype, which changes per-tile
        memory traffic — so the key carries it. The SC score path does very
        different per-tile work (integer popcount contraction vs MXU dot),
        so its variant keys its own bucket (``sc0`` = float)."""
        backend = backend or jax.default_backend()
        c = "causal" if causal else "full"
        return (f"flash:{backend}:{_mode(interpret, backend)}:b{b}:h{h}:kv{kv}"
                f":sq{sq}:skv{skv}:d{d}:{dtype}:{c}:sc{sc_bits or 0}")

    @staticmethod
    def paged_key(c: int, kv: int, g: int, d: int, block: int,
                  max_blocks: int, window: int | None, softcap: bool,
                  backend: str | None = None, interpret: bool | None = None,
                  dtype: str = "float32", sc_bits: int | None = None) -> str:
        """Key for the paged decode-attention kernel. The whole page-walk
        geometry is static per serving configuration (capacity, head
        layout, page size, table width), so it all goes in the key; the
        window / softcap flags change the masking work per step, and the
        SC variant (``sc<bits>``; ``sc0`` = float) swaps the contraction
        arithmetic entirely."""
        backend = backend or jax.default_backend()
        return (f"paged:{backend}:{_mode(interpret, backend)}:c{c}:kv{kv}"
                f":g{g}:d{d}:blk{block}:mb{max_blocks}:w{window or 0}"
                f":cap{int(softcap)}:{dtype}:sc{sc_bits or 0}")

    def _load(self) -> None:
        self._entries = self._read_disk()

    def _read_disk(self) -> dict[str, dict]:
        """Current on-disk entries; {} for a missing, torn, or foreign file.

        A torn/invalid document is never fatal — the affected keys simply
        re-tune (concurrent writers use atomic replace, so tearing should
        only come from crashes or foreign tools scribbling on the path).
        """
        try:
            doc = json.loads(self.path.read_text())
        except (OSError, ValueError):
            return {}
        if not isinstance(doc, dict) or doc.get("version") != CACHE_VERSION:
            # version 1 (or anything unknown): discard — v1 keys carried no
            # interpret flag, so the recorded timings' execution mode is
            # unknown and they must not seed either mode's dispatch.
            return {}
        entries = doc.get("entries")
        if not isinstance(entries, dict):
            return {}
        return {k: v for k, v in entries.items() if isinstance(v, dict)}

    def get(self, key: str, cls: type = KernelConfig):
        ent = self._entries.get(key)
        if ent is None:
            return None
        names = [f.name for f in dataclasses.fields(cls)]
        if any(f not in ent for f in names):
            return None
        cfg = cls(**{f: ent[f] for f in names})
        return cfg if cfg.is_valid() else None

    def put(self, key: str, cfg, *, elapsed_us: float | None = None) -> None:
        ent = asdict(cfg)
        ent["tuned_at"] = time.time()
        if elapsed_us is not None:
            ent["us_per_call"] = elapsed_us
        self._entries[key] = ent
        self._save()

    def _save(self) -> None:
        """Best-effort persist; an unwritable path degrades to in-memory.

        Concurrent-writer safe: the on-disk document is re-read and merged
        under this process's keys before the atomic replace, so two tuners
        sweeping different shapes interleave without losing each other's
        winners (last writer wins only on a genuinely shared key), and a
        reader never observes a torn file (write-to-temp + rename).
        """
        tmp = None
        try:
            self.path.parent.mkdir(parents=True, exist_ok=True)
            merged = self._read_disk()
            merged.update(self._entries)
            self._entries = merged
            doc = {"version": CACHE_VERSION, "entries": merged}
            fd, tmp = tempfile.mkstemp(dir=str(self.path.parent),
                                       prefix=self.path.name, suffix=".tmp")
            with os.fdopen(fd, "w") as f:
                json.dump(doc, f, indent=1, sort_keys=True)
            os.replace(tmp, self.path)
        except OSError:
            if tmp is not None:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass

    def __len__(self) -> int:
        return len(self._entries)


_DEFAULT_CACHES: dict[Path, AutotuneCache] = {}


def _default_cache() -> AutotuneCache:
    """Process-wide AutotuneCache per resolved path.

    Keyed on the path (not a singleton) so $REPRO_AUTOTUNE_CACHE changes take
    effect; reusing the instance keeps the hot tuned-matmul path free of
    per-call file reads — entries are served from memory after the first
    lookup.
    """
    path = default_cache_path()
    cache = _DEFAULT_CACHES.get(path)
    if cache is None:
        cache = _DEFAULT_CACHES[path] = AutotuneCache(path)
    return cache


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


# ------------------------------------------------------------ candidate grids

def candidate_configs(m: int, k: int, n: int, *,
                      vmem_budget: int = VMEM_BUDGET_BYTES
                      ) -> list[KernelConfig]:
    """Pruned SC-GEMM tuning grid for an (M, K, N) problem.

    Blocks larger than the (128-aligned) problem extent only add padding
    work, so they are dropped (the 128 block always stays); every candidate
    satisfies the VMEM budget and chunk | bk. Skinny (decode-shaped,
    M ≤ SKINNY_M_MAX) problems get one GEMV-like bm, the bucket itself: M
    lies on the kernel's lanes, where any larger block is pure padding.
    The residual chunk is one sublane tile (8 int32 rows).
    """
    m_cap = _round_up(max(m, 8), 128)
    n_cap = _round_up(max(n, 128), 128)
    k_cap = _round_up(max(k, 128), 128)
    bm_options = ((bucket_m(m),) if m <= SKINNY_M_MAX
                  else tuple(b for b in (128, 256) if b <= m_cap))
    out: list[KernelConfig] = []
    for bm in bm_options:
        for bn in (128, 256, 512):
            if bn > n_cap and bn != 128:
                continue
            for bk in (128, 256, 512):
                if bk > k_cap and bk != 128:
                    continue
                cfg = KernelConfig(bm=bm, bn=bn, bk=bk, chunk=8)
                if cfg.is_valid() and cfg.vmem_bytes() <= vmem_budget:
                    out.append(cfg)
    return out


def candidate_stream_configs(size: int) -> list[StreamConfig]:
    """Group widths for the stream-multiply kernel. Groups wider than the
    (128-element-row) problem only pad, so they are capped near the extent."""
    rows = max(_round_up(size, 128) // 128, 1)
    return [StreamConfig(block_rows=w)
            for w in (1, 2, 4, 8, 16, 32) if w <= rows]


def candidate_flash_configs(sq: int, skv: int, d: int, *,
                            vmem_budget: int = VMEM_BUDGET_BYTES
                            ) -> list[FlashConfig]:
    """(bq, bk) grid for the flash kernel: blocks must tile the (pre-padded)
    sequence extents exactly and fit the VMEM budget."""
    out = []
    for bq in (128, 256, 512):
        if sq % bq != 0:
            continue
        for bk in (128, 256, 512):
            if skv % bk != 0:
                continue
            cfg = FlashConfig(bq=bq, bk=bk)
            if cfg.is_valid() and cfg.vmem_bytes(d) <= vmem_budget:
                out.append(cfg)
    return out


def candidate_paged_configs(kv: int, g: int, d: int, *, block: int,
                            max_blocks: int,
                            vmem_budget: int = VMEM_BUDGET_BYTES,
                            sc: bool = False) -> list[PagedFlashConfig]:
    """KV-heads-per-step grid for the paged decode kernel: every divisor of
    the KV head count whose tiles + whole-row scratch fit the VMEM budget.

    Float full-MHA layouts (``g == 1``) drop ``kvh = 1`` — the whole-row
    score einsum that keeps ``g == 1`` in the bit-identity envelope needs
    ≥ 2 KV heads per grid step (a single-head slice lowers to a different
    contraction; see kernels/paged_attention.py, which rejects the combo).
    Single-KV-head full-MHA (``kv == 1``) therefore yields an empty grid,
    which the dispatch gate reads as "fall back to the gather path". The SC
    variant (``sc=True``) has no such restriction — its popcount
    contraction is elementwise, insensitive to head layout — so every
    divisor stays in the grid.
    """
    out = []
    for kvh in (1, 2, 4, 8, 16):
        if kv % kvh != 0 or kvh > kv:
            continue
        if g == 1 and kvh == 1 and not sc:
            continue
        cfg = PagedFlashConfig(kvh=kvh)
        if cfg.is_valid() and cfg.vmem_bytes(max_blocks=max_blocks,
                                             block=block, g=g,
                                             d=d) <= vmem_budget:
            out.append(cfg)
    return out


# -------------------------------------------------------------------- sweeps

def best_of_us(call: Callable[[], object], iters: int) -> float:
    """Best-of-``iters`` wall time (µs) of ``call`` after one warmup.

    Best-of, not mean: scheduler noise on shared machines only ever adds
    time. Shared by every tuner sweep and by ``benchmarks/sc_gemm.py``, so
    bench records and tuner decisions use one estimator.
    """
    call()  # compile
    best = float("inf")
    for _ in range(max(iters, 1)):
        t0 = time.perf_counter()
        call()
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def _sweep(cands: Sequence, time_one: Callable[[object], float],
           what: str):
    if not cands:
        raise ValueError(f"no tuning candidates for {what}")
    best_cfg, best_us = None, float("inf")
    for cfg in cands:
        us = time_one(cfg)
        if us < best_us:
            best_cfg, best_us = cfg, us
    return best_cfg, best_us


def _require_concrete(name: str, *arrays) -> None:
    if any(_is_tracer(a) for a in arrays):
        raise TypeError(
            f"{name}() needs concrete arrays: the sweep measures wall-clock "
            "time, which is meaningless for traced abstract values. Call it "
            "outside jax.jit, or go through the get_or_tune* entry points, "
            "which fall back to a synthetic-data sweep at trace time.")


def _sweep_outside_trace(fn: Callable[[], tuple]):
    """Run a tuning sweep from inside ``jax.jit`` tracing.

    JAX's trace context is thread-local, so a fresh worker thread sees no
    active trace: the sweep's (concrete, synthetic) operands execute eagerly
    instead of leaking into the caller's jaxpr, and the Pallas kernel
    tracing inside the timed calls never sees the caller's dynamic trace.
    """
    import concurrent.futures

    with concurrent.futures.ThreadPoolExecutor(max_workers=1) as ex:
        return ex.submit(fn).result()


#: Caps on the *synthetic* trace-time sweep operands. Under jit the logical
#: shape is the global (unsharded) one — a production train step can imply a
#: multi-million-row M — but block-config ranking is tile-local, so timing a
#: bounded slab ranks candidates the same while never materializing
#: global-batch-sized eager arrays at trace time. Candidate pruning still
#: uses the true shape; only the timed operands are capped.
SYNTH_M_CAP = 2048
SYNTH_KN_CAP = 8192


def _synth_normal(shape, seed: int) -> jax.Array:
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape), jnp.float32)


def _synth_mags(shape, bits: int, seed: int) -> jax.Array:
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, 1 << bits, size=shape), jnp.int32)


def _time_config(a, b, bits: int, cfg: KernelConfig, iters: int,
                 interpret: bool | None) -> float:
    from .ops import sc_matmul_pallas

    def call():
        return jax.block_until_ready(
            sc_matmul_pallas(a, b, bits=bits, bm=cfg.bm, bn=cfg.bn,
                             bk=cfg.bk, chunk=cfg.chunk, interpret=interpret))

    return best_of_us(call, iters)


def autotune(a, b, *, bits: int = 8,
             candidates: Sequence[KernelConfig] | None = None,
             iters: int = 3,
             max_candidates: int | None = None,
             interpret: bool | None = None) -> tuple[KernelConfig, float]:
    """Sweep the SC-GEMM grid on live data; return (best config, best µs)."""
    _require_concrete("autotune", a, b)
    m, k = a.shape
    _, n = b.shape
    cands: Iterable[KernelConfig] = (candidates if candidates is not None
                                     else candidate_configs(m, k, n))
    cands = list(cands)
    if max_candidates is not None:
        cands = cands[:max_candidates]
    return _sweep(cands,
                  lambda cfg: _time_config(a, b, bits, cfg, iters, interpret),
                  f"shape ({m},{k})x({k},{n})")


def get_or_tune(a, b, *, bits: int = 8,
                cache: AutotuneCache | None = None,
                candidates: Sequence[KernelConfig] | None = None,
                iters: int = 3,
                interpret: bool | None = None) -> KernelConfig:
    """Cached per-shape best SC-GEMM config; runs the sweep on a cache miss.

    Trace-safe: a cache hit needs only shapes; a miss under tracing sweeps
    synthetic operands (the tuned block configuration depends on the shape,
    not the values) whose extents are capped at (SYNTH_M_CAP, SYNTH_KN_CAP)
    — candidates are still pruned against the true shape, but the timed slab
    stays bounded even when the traced global shape is production-sized.

    Skinny (decode-shaped) M is bucketed: the key, the candidate grid, and
    the synthetic sweep all use ``bucket_m(m)``, so one GEMV-like winner
    serves every live batch size in the bucket.
    """
    m, k = a.shape
    _, n = b.shape
    m = bucket_m(m)
    cache = cache if cache is not None else _default_cache()
    key = cache.key(m, k, n, bits, interpret=interpret)
    hit = cache.get(key, KernelConfig)
    if hit is not None:
        return hit
    if _is_tracer(a) or _is_tracer(b):
        cands = (list(candidates) if candidates is not None
                 else candidate_configs(m, k, n))
        ms = min(m, SYNTH_M_CAP)
        ks, ns = min(k, SYNTH_KN_CAP), min(n, SYNTH_KN_CAP)
        cfg, us = _sweep_outside_trace(lambda: autotune(
            _synth_normal((ms, ks), seed=m * 7919 + k),
            _synth_normal((ks, ns), seed=k * 7919 + n),
            bits=bits, candidates=cands, iters=iters,
            interpret=interpret))
    else:
        cfg, us = autotune(a, b, bits=bits, candidates=candidates,
                           iters=iters, interpret=interpret)
    cache.put(key, cfg, elapsed_us=us)
    return cfg


# ------------------------------------------------------- stream-kernel sweep

def _time_stream_config(x, y, bits: int, cfg: StreamConfig, iters: int,
                        interpret: bool | None) -> float:
    from .ops import sc_stream_mul

    def call():
        return jax.block_until_ready(
            sc_stream_mul(x, y, bits=bits, block_rows=cfg.block_rows,
                          interpret=interpret))

    return best_of_us(call, iters)


def get_or_tune_stream(x, y, *, bits: int = 8,
                       cache: AutotuneCache | None = None,
                       candidates: Sequence[StreamConfig] | None = None,
                       iters: int = 3,
                       interpret: bool | None = None) -> StreamConfig:
    """Cached best rows-per-call group width for ``ops.sc_stream_mul``."""
    size = int(np.prod(x.shape)) if x.shape else 1
    cache = cache if cache is not None else _default_cache()
    key = cache.stream_key(size, bits, interpret=interpret)
    hit = cache.get(key, StreamConfig)
    if hit is not None:
        return hit
    cands = (list(candidates) if candidates is not None
             else candidate_stream_configs(size))
    if _is_tracer(x) or _is_tracer(y):
        # synthetic slab capped like the GEMM sweep: group-width ranking is
        # rows-local, so a bounded flat size ranks candidates the same
        slab = (min(size, SYNTH_M_CAP * 128),)
        xs = _synth_mags(slab, bits, seed=size)
        ys = _synth_mags(slab, bits, seed=size + 1)
        cfg, us = _sweep_outside_trace(lambda: _sweep(
            cands,
            lambda c: _time_stream_config(xs, ys, bits, c, iters, interpret),
            f"stream size {size}"))
    else:
        cfg, us = _sweep(
            cands,
            lambda c: _time_stream_config(x, y, bits, c, iters, interpret),
            f"stream size {size}")
    cache.put(key, cfg, elapsed_us=us)
    return cfg


# -------------------------------------------------------- flash-kernel sweep

def _time_flash_config(q, k, v, causal: bool, cfg: FlashConfig, iters: int,
                       interpret: bool | None,
                       sc_bits: int | None = None) -> float:
    from .flash_attention import flash_attention_pallas
    from .ops import default_interpret

    interp = default_interpret() if interpret is None else interpret

    def call():
        return jax.block_until_ready(
            flash_attention_pallas(q, k, v, causal=causal, bq=cfg.bq,
                                   bk=cfg.bk, interpret=interp,
                                   sc_bits=sc_bits))

    return best_of_us(call, iters)


def get_or_tune_flash(q, k, v, *, causal: bool = True,
                      cache: AutotuneCache | None = None,
                      candidates: Sequence[FlashConfig] | None = None,
                      iters: int = 3,
                      interpret: bool | None = None,
                      sc_bits: int | None = None) -> FlashConfig:
    """Cached best (bq, bk) for the flash kernel at this problem shape.

    ``q: (B, H, Sq, D)``; ``k, v: (B, KV, Skv, D)`` — the kernel layout.
    The SC score path (``sc_bits``) sweeps and caches its own bucket: the
    popcount contraction's block-size trade-offs are unrelated to the MXU
    dot's.
    """
    b, h, sq, d = q.shape
    _, kv, skv, _ = k.shape
    dtype = jnp.dtype(q.dtype).name
    cache = cache if cache is not None else _default_cache()
    key = cache.flash_key(b, h, kv, sq, skv, d, causal, interpret=interpret,
                          dtype=dtype, sc_bits=sc_bits)
    hit = cache.get(key, FlashConfig)
    if hit is not None:
        return hit
    cands = (list(candidates) if candidates is not None
             else candidate_flash_configs(sq, skv, d))
    what = f"flash ({b},{h},{sq},{d})x(kv={kv},{skv})"
    if any(_is_tracer(t) for t in (q, k, v)):
        # (bq, bk) ranking depends on (sq, skv, d), which must be exact for
        # divisibility; batch/head extents only scale the grid, so cap them
        # to bound the synthetic slab at trace time.
        g = max(h // max(kv, 1), 1)
        kv_c = min(kv, 2)
        b_c, h_c = min(b, 2), g * kv_c
        # synthetic operands keep the caller's dtype: bf16 halves per-tile
        # memory traffic, so the (bq, bk) ranking is dtype-dependent
        qs = _synth_normal((b_c, h_c, sq, d), seed=sq * 31 + d).astype(q.dtype)
        ks = _synth_normal((b_c, kv_c, skv, d), seed=skv * 31 + d).astype(q.dtype)
        vs = _synth_normal((b_c, kv_c, skv, d), seed=skv * 37 + d).astype(q.dtype)
        cfg, us = _sweep_outside_trace(lambda: _sweep(
            cands,
            lambda c: _time_flash_config(qs, ks, vs, causal, c, iters,
                                         interpret, sc_bits), what))
    else:
        cfg, us = _sweep(
            cands,
            lambda c: _time_flash_config(q, k, v, causal, c, iters,
                                         interpret, sc_bits), what)
    cache.put(key, cfg, elapsed_us=us)
    return cfg


# ------------------------------------------------- paged-attention sweep

def _time_paged_config(q, kp, vp, tables, qpos, window, softcap,
                       cfg: PagedFlashConfig, iters: int,
                       interpret: bool | None,
                       sc_bits: int | None = None) -> float:
    from .ops import default_interpret
    from .paged_attention import paged_attention_pallas

    interp = default_interpret() if interpret is None else interpret

    def call():
        return jax.block_until_ready(
            paged_attention_pallas(q, kp, vp, tables, qpos, window=window,
                                   logit_softcap=softcap, kvh=cfg.kvh,
                                   interpret=interp, sc_bits=sc_bits))

    return best_of_us(call, iters)


#: Synthetic-sweep cap on the slot (capacity) extent: the grid scales
#: linearly in it, so ranking kvh candidates on a few slots ranks them for
#: any capacity while bounding trace-time sweep work.
SYNTH_C_CAP = 8


def get_or_tune_paged(q, k_pages, v_pages, tables, q_positions, *,
                      window: int | None = None,
                      logit_softcap: float | None = None,
                      cache: AutotuneCache | None = None,
                      candidates: Sequence[PagedFlashConfig] | None = None,
                      iters: int = 3,
                      interpret: bool | None = None,
                      sc_bits: int | None = None) -> PagedFlashConfig:
    """Cached best KV-heads-per-step for the paged decode-attention kernel.

    ``q: (C, KV, G, D)``; ``k_pages, v_pages: (P, block, KV, D)``;
    ``tables: (C, MB)`` — the kernel layout. Trace-safe like the other
    tuners: a hit resolves from shape alone; a miss under tracing sweeps a
    synthetic page pool (capacity capped at :data:`SYNTH_C_CAP`, every page
    live so the walk does worst-case work).
    """
    c, kv, g, d = q.shape
    n_pages, block = k_pages.shape[0], k_pages.shape[1]
    max_blocks = tables.shape[1]
    dtype = jnp.dtype(q.dtype).name
    cache = cache if cache is not None else _default_cache()
    key = cache.paged_key(c, kv, g, d, block, max_blocks, window,
                          logit_softcap is not None, interpret=interpret,
                          dtype=dtype, sc_bits=sc_bits)
    hit = cache.get(key, PagedFlashConfig)
    if hit is not None:
        return hit
    cands = (list(candidates) if candidates is not None
             else candidate_paged_configs(kv, g, d, block=block,
                                          max_blocks=max_blocks,
                                          sc=sc_bits is not None))
    what = f"paged (c={c},kv={kv},g={g},d={d}) blk{block}x{max_blocks}"
    if any(_is_tracer(t) for t in (q, k_pages, v_pages, tables, q_positions)):
        c_s = min(c, SYNTH_C_CAP)
        p_s = min(n_pages, c_s * max_blocks + 1)
        dt = q.dtype

        def synth_sweep():
            # built inside the worker thread: array creation on the tracing
            # thread would stage constants into the caller's trace and leak
            qs = _synth_normal((c_s, kv, g, d), seed=kv * 31 + d).astype(dt)
            ks = _synth_normal((p_s, block, kv, d),
                               seed=block * 31 + d).astype(dt)
            vs = _synth_normal((p_s, block, kv, d),
                               seed=block * 37 + d).astype(dt)
            # fully-allocated fragmented tables + max positions: every grid
            # step does real work, so the sweep ranks worst-case walk cost
            tbl = jnp.asarray(
                (np.arange(c_s * max_blocks, dtype=np.int64) * 7919
                 % max(p_s - 1, 1)).reshape(c_s, max_blocks).astype(np.int32))
            qp = jnp.full((c_s,), max_blocks * block - 1, jnp.int32)
            return _sweep(
                cands,
                lambda cf: _time_paged_config(qs, ks, vs, tbl, qp, window,
                                              logit_softcap, cf, iters,
                                              interpret, sc_bits), what)

        cfg, us = _sweep_outside_trace(synth_sweep)
    else:
        cfg, us = _sweep(
            cands,
            lambda cf: _time_paged_config(q, k_pages, v_pages, tables,
                                          q_positions, window, logit_softcap,
                                          cf, iters, interpret, sc_bits),
            what)
    cache.put(key, cfg, elapsed_us=us)
    return cfg


def choose_impl(m: int, k: int, n: int, *, bits: int = 8) -> str:
    """Implementation choice behind ``sc_matmul(..., impl="auto")``.

    On TPU the Pallas kernel with autotuned blocks wins for every shape large
    enough to tile — including decode-shaped (skinny-M) GEMMs, which resolve
    to a skinny-bucket GEMV-like config instead of the prefill tile as long
    as the K·N face is MXU-sized. Tiny problems and non-TPU backends (where
    Pallas runs in interpret mode) fall back to the XLA-fused MXU split.
    """
    if jax.default_backend() == "tpu":
        if min(m, n) * k >= 128 * 128:
            return "pallas_tuned"
        if m <= SKINNY_M_MAX and k * n >= 128 * 128:
            return "pallas_tuned"
    return "mxu_split"
