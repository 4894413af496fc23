"""Layer-2 jaxpr contract audits (DESIGN.md §11).

The AST lints catch what code *says*; these audits catch what the compiler
will *do*.  Each audit traces a representative shape with
``jax.make_jaxpr`` (no device execution except the compile-count audit,
which runs a short engine schedule on the tiny CPU config) and asserts a
structural property of the resulting jaxpr:

* :func:`audit_popcount_path` — the deterministic-SC claim.  The packed
  stream kernel must lower to integer-only ops, and the SC-GEMM closed
  form must contain no half-precision ``convert_element_type`` anywhere:
  a single injected cast breaks count-identity with the paper's
  AND-gate/popcount multiplier.
* :func:`audit_einsum_parity` — the paged kernel's bit-identity envelope.
  The fused decode kernel's score/PV contractions must have exactly the
  dense gathered path's ``dot_general`` dimension orders (and fp32
  outputs), for both the GQA and the full-MHA (g == 1 whole-row finish)
  geometries.
* :func:`audit_compile_counts` — the bounded-executables contract from
  chunked prefill: a mixed-length schedule compiles at most one prefill
  executable per prompt bucket and exactly one decode executable (zero
  decode recompiles after warmup).
* :func:`audit_cow_protocol` — the prefix-cache sharing contract
  (DESIGN.md §12): driving a shared-prefix schedule step by step, every
  live slot's next write page is *writable* (refcount ≤ 1 and not
  prefix-retained) at every decode step — no write ever lands in a shared
  page without a preceding copy — refcounts equal the block-table
  references plus staging pins throughout, the schedule actually
  exercises sharing (hits and a CoW copy), and the drain leaks nothing.

Run as ``PYTHONPATH=src python -m repro.analysis.contracts`` (CI's
``analysis`` job); exit 1 on any violated contract.
"""
from __future__ import annotations

import sys
from typing import Any, Callable, Iterator

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["iter_eqns", "half_precision_casts", "contraction_dims",
           "audit_popcount_path", "audit_einsum_parity",
           "audit_compile_counts", "run_audits", "main"]

_HALF = (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float16))


# --------------------------------------------------------------- jaxpr walk

def _subjaxprs(val: Any) -> Iterator[Any]:
    from jax.extend import core
    if isinstance(val, core.Jaxpr):
        yield val
    elif isinstance(val, core.ClosedJaxpr):
        yield val.jaxpr
    elif isinstance(val, (tuple, list)):
        for v in val:
            yield from _subjaxprs(v)


def iter_eqns(jaxpr: Any) -> Iterator[Any]:
    """Every eqn in a (Closed)Jaxpr, recursing through call/scan/pallas
    sub-jaxprs found in eqn params."""
    for j in _subjaxprs(jaxpr):
        for eqn in j.eqns:
            yield eqn
            for param in eqn.params.values():
                for sub in _subjaxprs(param):
                    yield from iter_eqns(sub)


def half_precision_casts(fn: Callable, *args, **kwargs) -> list[str]:
    """Lines describing every 16-bit-float convert_element_type in fn's
    jaxpr (empty == the path is cast-free)."""
    jaxpr = jax.make_jaxpr(fn)(*args, **kwargs)
    return [f"convert_element_type -> {eqn.params['new_dtype']}"
            for eqn in iter_eqns(jaxpr)
            if eqn.primitive.name == "convert_element_type"
            and jnp.dtype(eqn.params["new_dtype"]) in _HALF]


def contraction_dims(fn: Callable, *args, **kwargs) -> list[tuple]:
    """(dimension_numbers, out_dtype) of every dot_general in fn's jaxpr."""
    jaxpr = jax.make_jaxpr(fn)(*args, **kwargs)
    return [(eqn.params["dimension_numbers"],
             jnp.dtype(eqn.outvars[0].aval.dtype))
            for eqn in iter_eqns(jaxpr)
            if eqn.primitive.name == "dot_general"]


# ------------------------------------------------------------------ audits

def audit_popcount_path(bits: int = 8) -> list[str]:
    """No float ops in the packed stream kernel; no half-precision casts
    in the SC-GEMM closed form."""
    from repro.core.sc_matmul import sc_matmul_mxu_split, sc_matmul_reference
    from repro.kernels.sc_bitops import sc_stream_mul_pallas

    problems: list[str] = []

    x = jnp.zeros((8, 128), jnp.int32)
    stream = lambda a, b: sc_stream_mul_pallas(a, b, bits=bits,
                                               interpret=True)
    jaxpr = jax.make_jaxpr(stream)(x, x)
    for eqn in iter_eqns(jaxpr):
        for out in eqn.outvars:
            dt = getattr(getattr(out, "aval", None), "dtype", None)
            if dt is not None and not jnp.issubdtype(dt, jnp.integer) \
                    and not jnp.issubdtype(dt, jnp.bool_):
                problems.append(
                    f"popcount path: {eqn.primitive.name} produces {dt} — "
                    f"the packed stream kernel must be integer-only")

    a = jnp.zeros((16, 32), jnp.float32)
    b = jnp.zeros((32, 8), jnp.float32)
    for name, fn in (("sc_matmul_reference", sc_matmul_reference),
                     ("sc_matmul_mxu_split", sc_matmul_mxu_split)):
        for cast in half_precision_casts(
                lambda l, r: fn(l, r, bits=bits), a, b):
            problems.append(f"{name}: {cast} on the SC popcount path")
    return problems


def _paged_args(c: int, kv: int, g: int, d: int, block: int,
                max_blocks: int):
    n_pages = c * max_blocks + 1                      # + trash block
    q = jnp.zeros((c, kv, g, d), jnp.float32)
    k_pages = jnp.zeros((n_pages, block, kv, d), jnp.float32)
    tables = jnp.tile(jnp.arange(max_blocks, dtype=jnp.int32), (c, 1))
    pos = jnp.full((c,), block + 1, jnp.int32)
    return q, k_pages, k_pages, tables, pos


def audit_einsum_parity() -> list[str]:
    """Fused paged kernel contractions == gathered-dense contractions."""
    from repro.kernels.paged_attention import paged_attention_pallas
    from repro.models.layers import decode_attention

    problems: list[str] = []
    for label, (kv, g) in (("GQA", (2, 2)), ("full-MHA", (4, 1))):
        c, d, block, max_blocks = 2, 16, 8, 2
        args = _paged_args(c, kv, g, d, block, max_blocks)
        kernel = lambda *a: paged_attention_pallas(*a, kvh=kv,
                                                   interpret=True)
        kernel_dims = contraction_dims(kernel, *args)

        s = block * max_blocks
        q = jnp.zeros((c, 1, kv * g, d), jnp.float32)
        cache = jnp.zeros((c, s, kv, d), jnp.float32)
        pos = jnp.full((c,), block + 1, jnp.int32)
        dense = lambda q_, k_, v_, p_: decode_attention(
            q_, k_, v_, q_position=p_)
        dense_dims = contraction_dims(dense, q, cache, cache, pos)

        if sorted(set(d_ for d_, _ in kernel_dims)) != \
                sorted(set(d_ for d_, _ in dense_dims)):
            problems.append(
                f"einsum parity ({label}): paged kernel dot_general dims "
                f"{sorted(set(d_ for d_, _ in kernel_dims))} != dense path "
                f"{sorted(set(d_ for d_, _ in dense_dims))}")
        for source, dims in (("paged kernel", kernel_dims),
                             ("dense path", dense_dims)):
            for dnums, dtype in dims:
                if dtype != jnp.dtype(jnp.float32):
                    problems.append(
                        f"einsum parity ({label}): {source} contraction "
                        f"accumulates in {dtype}, not float32")
    return problems


def audit_compile_counts() -> list[str]:
    """A mixed-length engine schedule stays within the bucket-bounded
    prefill executable count and never recompiles decode after warmup."""
    from repro.configs.base import ModelConfig
    from repro.models import bind
    from repro.serving import Engine, Request

    cfg = ModelConfig(
        name="contract-audit-dense", family="dense", n_layers=2, d_model=64,
        n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128, vocab_size=128,
        dtype="float32", q_block=16, kv_block=16, loss_chunk=16,
        remat=False, use_sc_gemm=True).validate()
    params = bind(cfg).init_params(jax.random.PRNGKey(0))

    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, size=(s,)).astype(np.int32)
               for s in (3, 5, 9, 12)]
    requests = [Request(uid=f"audit-{i}", prompt=p, max_new_tokens=4)
                for i, p in enumerate(prompts)]

    engine = Engine(cfg, params, capacity=2, max_seq=32, chunk=4)
    engine.run(requests)

    problems: list[str] = []
    n_exec = engine.stats["prefill_executables"]
    buckets = engine.stats["buckets"]
    if n_exec > len(buckets):
        problems.append(
            f"compile count: {n_exec} prefill executables exceeds the "
            f"bucket bound len({buckets}) = {len(buckets)}")

    decode_execs = engine._decode._cache_size()
    if decode_execs != 1:
        problems.append(
            f"compile count: decode step holds {decode_execs} executables "
            f"after the schedule — expected exactly 1 (zero recompiles "
            f"after warmup)")
    return problems


def audit_cow_protocol() -> list[str]:
    """A shared-prefix schedule never writes into a refcount>1 (or
    prefix-retained) page without a preceding copy, and the refcount
    ledger stays consistent with the block tables + staging pins."""
    from repro.configs.base import ModelConfig
    from repro.models import bind
    from repro.serving import Engine, Request

    cfg = ModelConfig(
        name="contract-audit-prefix", family="dense", n_layers=2,
        d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
        vocab_size=128, dtype="float32", q_block=16, kv_block=16,
        loss_chunk=16, remat=False, use_sc_gemm=True).validate()
    params = bind(cfg).init_params(jax.random.PRNGKey(0))

    rng = np.random.default_rng(11)
    base = rng.integers(0, cfg.vocab_size, size=(16,)).astype(np.int32)
    prompts = [base.copy(), base.copy(),               # identical → CoW
               np.concatenate([base[:8],               # divergent suffix
                               rng.integers(0, cfg.vocab_size, size=(6,))
                               .astype(np.int32)])]
    requests = [Request(uid=f"cow-{i}", prompt=p, max_new_tokens=5)
                for i, p in enumerate(prompts)]

    # block > chunk so the chunk-aligned resume lands mid-page and the
    # aligned full match forces a real paged_copy_page at admission
    engine = Engine(cfg, params, capacity=2, max_seq=32, block=8, chunk=4)
    pool = engine.pool
    for r in requests:
        engine.queue.submit(r)

    problems: list[str] = []

    def check_step(step_ix: int) -> None:
        refs = np.zeros(pool.n_blocks, np.int64)
        for slot in pool.entries:
            live = pool.tables[slot][pool.tables[slot] >= 0]
            np.add.at(refs, live, 1)
        st = engine._staging
        if st is not None and st.match is not None:
            np.add.at(refs, np.asarray(st.match.pages), 1)
        if not np.array_equal(refs, pool.refcount):
            problems.append(
                f"cow protocol: step {step_ix}: refcount ledger "
                f"{pool.refcount.tolist()} != table references + pins "
                f"{refs.tolist()}")
        for slot, entry in pool.entries.items():
            page = int(pool.tables[slot, entry.next_write_pos // pool.block])
            if page >= 0 and not pool.writable(page):
                problems.append(
                    f"cow protocol: step {step_ix}: slot {slot} "
                    f"({entry.request.uid!r}) would write page {page} with "
                    f"refcount {int(pool.refcount[page])} "
                    f"(retained={page in pool.retained}) without a copy")

    step_ix = 0
    check_step(step_ix)
    while engine.step():
        step_ix += 1
        check_step(step_ix)

    if engine._n_prefix_hits < 2:
        problems.append(
            f"cow protocol: schedule produced {engine._n_prefix_hits} "
            f"prefix hits — the audit never exercised sharing")
    if pool.n_cow < 1:
        problems.append(
            "cow protocol: schedule produced no CoW copy — the aligned "
            "full match must copy the resume page at admission")
    if (pool.refcount != 0).any():
        problems.append(
            f"cow protocol: drained pool leaks references "
            f"{pool.refcount.tolist()}")
    if pool.free_pages + len(pool.retained) != pool.n_blocks:
        problems.append(
            f"cow protocol: drained pool leaks pages — {pool.free_pages} "
            f"free + {len(pool.retained)} retained != {pool.n_blocks}")
    return problems


# -------------------------------------------------------------------- main

AUDITS: tuple[tuple[str, Callable[[], list[str]]], ...] = (
    ("popcount-path", audit_popcount_path),
    ("einsum-parity", audit_einsum_parity),
    ("compile-counts", audit_compile_counts),
    ("cow-protocol", audit_cow_protocol),
)


def run_audits() -> list[str]:
    problems: list[str] = []
    for name, audit in AUDITS:
        found = audit()
        status = "FAIL" if found else "PASS"
        print(f"[{status}] contract audit: {name}")
        for p in found:
            print(f"       {p}")
        problems.extend(found)
    return problems


def main() -> int:
    problems = run_audits()
    n = len(problems)
    print(f"repro-analysis contracts: {n} violation{'' if n == 1 else 's'}")
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
