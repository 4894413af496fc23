"""Operations and bytes of a mixture of experts' routed experts, computed
from shapes alone, and the share of their roofline the device trace
shows (``moe_gmm_roofline``).

Nothing here reads the program. Per program execution that computes
``rows`` token rows, the routed experts must at least

* multiply: ``2 · rows · k · 3 · d · f`` operations per MoE layer (each
  row's ``k`` experts, three ``d × f`` matrices each);
* read every expert that some row chose, once: with ``k`` of ``E`` chosen
  uniformly, ``E · (1 − (1 − k/E)^rows)`` experts are hit on average, each
  ``3 · d · f`` weights of ``itemsize`` bytes, per MoE layer.

The least time of an execution is the larger of the operations at the
bf16 peak and the bytes at the HBM peak.
"""
from __future__ import annotations

__all__ = ["moe_layers", "expert_flops", "expert_bytes", "least_seconds",
           "window_rows", "gmm_seconds", "gmm_roofline", "PROGRAMS",
           "KERNEL"]

#: The grouped-matmul kernel's op name in a device trace (the TPU Pallas
#: ``megablox`` kernel's custom call).
KERNEL = "gmm"

#: Step programs by the rows they compute: the batched decode (one row per
#: generated token) and a prefill chunk (a prompt's rows).
PROGRAMS = {"decode": "jit_decode", "prefill": "jit_step"}


def moe_layers(s) -> int:
    return s.layers - s.dense_layers


def expert_flops(s, rows: float) -> float:
    return 2.0 * rows * s.top_k * 3 * s.d * s.moe_ff * moe_layers(s)


def expert_bytes(s, rows: float, itemsize: int = 2) -> float:
    hit = s.experts * (1.0 - (1.0 - s.top_k / s.experts) ** rows)
    return hit * 3 * s.d * s.moe_ff * itemsize * moe_layers(s)


def least_seconds(s, rows: float, peak: dict, itemsize: int = 2) -> float:
    return max(expert_flops(s, rows) / peak["bf16_flops_per_s"],
               expert_bytes(s, rows, itemsize) / peak["hbm_bytes_per_s"])


def window_rows(run, t0: float, t1: float) -> dict:
    """Token rows behind the tokens that arrived in ``[t0, t1)``, counted as
    ``readings.model_flops`` counts them: a decode token is one row, a first
    token its whole prompt."""
    rows = {"decode": 0, "prefill": 0}
    for uid, times in run.tokens.times.items():
        p = len(run.spec_by_uid[uid].prompt)
        for j, t in enumerate(times):
            if t0 <= t < t1:
                rows["prefill" if j == 0 else "decode"] += p if j == 0 else 1
    return rows


def gmm_seconds(top_ops) -> float:
    """Device seconds of the grouped-matmul kernel in a trace's full list
    of ``[program/op, seconds]``."""
    return sum(sec for name, sec in top_ops
               if name.rsplit("/", 1)[-1] == KERNEL)


def gmm_roofline(run) -> float | None:
    """Percent: the least time of the traced window's routed-expert work
    (each step program's executions at their mean rows) over the device
    seconds of the grouped-matmul kernel; None without a trace, a kernel
    or a mixture of experts."""
    tr = run.trace
    s = run.sizes
    if tr is None or not hasattr(s, "experts"):
        return None
    seconds = gmm_seconds(tr.top_ops)
    if seconds <= 0:
        return None
    itemsize = 2 if run.config_file["model"]["torch_dtype"] == "bfloat16" \
        else 4
    rows = window_rows(run, tr.host_start, tr.host_end)
    least = 0.0
    for kind, module in PROGRAMS.items():
        n = tr.modules.get(module, 0)
        if n and rows[kind]:
            least += n * least_seconds(s, rows[kind] / n, run.peak, itemsize)
    return 100.0 * least / seconds if least else None
