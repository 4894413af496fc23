"""The yardstick's arithmetic: operations and bytes of the model step and of
one SC-GEMM call, computed from shapes alone, and the table of peaks.

Nothing here reads the program. A later change that makes the program do
less or more work leaves these numbers as they are, so a share of a peak
can only rise by doing the same work in less time.
"""
from __future__ import annotations

import json
from pathlib import Path

__all__ = ["peaks", "projection_shapes", "projection_params", "row_flops",
           "request_flops", "sc_call_cost", "sc_least_seconds",
           "kv_bytes_per_token", "gathered_view_bytes"]

HERE = Path(__file__).resolve().parent


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; an unknown device is
    an error, never a default."""
    table = json.loads((HERE / "peaks.json").read_text())
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table['devices'])}") from None


def projection_shapes(s) -> list[tuple[int, int]]:
    """``(K, N)`` of one layer's dense projections: Q, K, V, O, gate, up,
    down."""
    d, hd = s.d, s.head_dim
    return [(d, s.heads * hd), (d, s.kv_heads * hd), (d, s.kv_heads * hd),
            (s.heads * hd, d), (d, s.ff), (d, s.ff), (s.ff, d)]


def projection_params(s) -> int:
    """Weights of the dense projections of every layer (no embedding)."""
    return s.layers * sum(k * n for k, n in projection_shapes(s))


def row_flops(s, context: int, sampled: bool) -> int:
    """Model FLOPs of one token row: 2 per projection weight, the QK and PV
    products over ``context`` key positions in every layer, and the head
    when the row's logits are sampled."""
    attn = 4 * s.layers * s.heads * s.head_dim * context
    head = 2 * s.d * s.vocab if sampled else 0
    return 2 * projection_params(s) + attn + head


def request_flops(s, start: int, end: int, sampled_rows: int) -> int:
    """Model FLOPs of rows at positions ``[start, end)`` of one sequence
    (context ``pos + 1`` each), ``sampled_rows`` of which are sampled."""
    n = max(end - start, 0)
    contexts = (end * (end + 1) - start * (start + 1)) // 2
    return (n * 2 * projection_params(s)
            + 4 * s.layers * s.heads * s.head_dim * contexts
            + sampled_rows * 2 * s.d * s.vocab)


def sc_call_cost(m: int, k: int, n: int, bits: int) -> tuple[int, float]:
    """(operations, bytes) any implementation of one ``(M, K) @ (K, N)``
    SC-GEMM call must at least spend: one multiply-add per pair, and each
    ``bits``-wide operand read once plus the bfloat16 output written once."""
    return 2 * m * k * n, (m * k + k * n) * bits / 8 + m * n * 2


def sc_least_seconds(calls, bits: int, peak: dict) -> float:
    """Σ over ``calls`` (``(M, K, N, count)``) of the larger of operations at
    the int8 peak and bytes at the HBM peak."""
    total = 0.0
    for m, k, n, count in calls:
        ops, nbytes = sc_call_cost(m, k, n, bits)
        total += count * max(ops / peak["int8_ops_per_s"],
                             nbytes / peak["hbm_bytes_per_s"])
    return total


def kv_bytes_per_token(s, itemsize: int = 2) -> int:
    """K and V bytes one token holds over every layer."""
    return 2 * s.layers * s.kv_heads * s.head_dim * itemsize


def gathered_view_bytes(s, capacity: int, max_blocks: int, block: int,
                        itemsize: int = 2) -> int:
    """Bytes of the dense per-slot view a gathered paged decode builds for
    every layer at once: ``capacity × max_blocks·block`` positions of K and
    V (the same shape arithmetic as the program's gather)."""
    return capacity * max_blocks * block * kv_bytes_per_token(s, itemsize)
