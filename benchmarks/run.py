# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark entry point: Table II (hardware + MAE), Fig 1(b) (error
distribution), SC-GEMM microbenchmarks, and the dry-run roofline report.

    PYTHONPATH=src python -m benchmarks.run [--only table2,fig1b,sc_gemm,roofline]
                                            [--smoke] [--json PATH]

Every run that includes the ``sc_gemm`` suite appends a timestamped record to
the ``BENCH_sc_gemm.json`` trajectory (repo root by default, ``--json`` to
relocate), so per-impl timings accumulate across commits. The smoke grid
includes a decode-shaped (M = batch, S = 1) problem so the skinny autotune
bucket is exercised per commit; the serving engine has its own trajectory
(``python -m benchmarks.serving``, BENCH_serving.json).
"""
from __future__ import annotations

import argparse
import json
import subprocess
import sys
import time
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent
DEFAULT_TRAJECTORY = REPO_ROOT / "BENCH_sc_gemm.json"


def git_sha() -> str | None:
    """Short HEAD SHA of the repo (with a ``-dirty`` marker when the working
    tree has uncommitted changes, so a record is never attributed to code
    the named commit did not contain), or None outside a checkout."""
    try:
        out = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                             cwd=REPO_ROOT, capture_output=True, text=True,
                             timeout=10)
        sha = out.stdout.strip()
        if not sha:
            return None
        status = subprocess.run(["git", "status", "--porcelain"],
                                cwd=REPO_ROOT, capture_output=True, text=True,
                                timeout=10)
        return sha + ("-dirty" if status.stdout.strip() else "")
    except (OSError, subprocess.SubprocessError):
        return None


def append_trajectory(path: Path, rows: list[dict], *, smoke: bool) -> None:
    """Append one run record to the JSON trajectory file.

    Each record carries the git SHA, backend, and interpret flag so
    ``benchmarks.check_regression`` can compare like with like (interpret-mode
    CPU timings are meaningless against compiled TPU ones).
    """
    import jax

    from repro.kernels.ops import default_interpret
    doc = {"runs": []}
    try:
        loaded = json.loads(path.read_text())
        if isinstance(loaded, dict) and isinstance(loaded.get("runs"), list):
            doc = loaded
    except (OSError, ValueError):
        pass
    import os
    import platform
    doc["runs"].append({
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
        "backend": jax.default_backend(),
        "git_sha": git_sha(),
        "interpret": default_interpret(),
        # informational only (not part of the regression-gate signature):
        # flags cross-machine baselines when a gate failure looks suspicious
        "host": platform.node(),
        "cpus": os.cpu_count(),
        "smoke": smoke,
        "rows": rows,
    })
    path.write_text(json.dumps(doc, indent=1) + "\n")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of benchmarks to run")
    ap.add_argument("--smoke", action="store_true",
                    help="small shapes / capped tuning sweeps (CI)")
    ap.add_argument("--json", type=Path, default=DEFAULT_TRAJECTORY,
                    help="sc_gemm trajectory file (default: repo root)")
    args = ap.parse_args()

    from repro.launch import setup_compile_cache
    setup_compile_cache()

    from . import fig1b, roofline, sc_gemm, table2
    suites = {"table2": table2.run, "fig1b": fig1b.run,
              "sc_gemm": lambda: sc_gemm.run(smoke=args.smoke),
              "roofline": roofline.run}
    selected = (args.only.split(",") if args.only else list(suites))

    print("name,us_per_call,derived")
    failures = 0
    for key in selected:
        try:
            rows = suites[key]()
            for row in rows:
                derived = str(row["derived"]).replace(",", ";")
                print(f"{row['name']},{row['us_per_call']},{derived}")
            if key == "sc_gemm":
                try:
                    append_trajectory(args.json, rows, smoke=args.smoke)
                    print(f"sc_gemm/trajectory,0,appended to {args.json.name}",
                          file=sys.stderr)
                except OSError as e:
                    # The history append is optional; a read-only checkout
                    # must not fail a benchmark run that already succeeded.
                    print(f"sc_gemm/trajectory,0,NOT appended "
                          f"({type(e).__name__}: {e})", file=sys.stderr)
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{key}/ERROR,0,{type(e).__name__}: {e}", file=sys.stderr)
    if failures:
        raise SystemExit(failures)


if __name__ == "__main__":
    main()
