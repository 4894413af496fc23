"""The arrival-rate sweep that places an open-loop cell: the same cell at
several offered rates, in one process on the chip.

    python3 bench/sweep.py --workload bf16-chat --rates 1.2,1.6,2.0 \
        --seconds 40 --seed 7

For each rate it prints one JSON line: tokens/s, TTFT p50/p95, ITL p95,
how many requests due in the window had no first token when it closed, and
the TTFT median of the window's last third over its first third (a backlog
that grows all through the window reads well above 1). The rate a cell
runs at is fixed in its mix file; this only finds it.
"""
from __future__ import annotations

import argparse
import copy
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    from bench import cell, readings, run, work
    spec = cell.load(ROOT, args.workload)
    run.use_checkout_cache()
    import jax
    import numpy as np
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    peak = work.peaks(devices[0].device_kind)
    records: list = []
    for rate in (float(r) for r in args.rates.split(",")):
        s = copy.deepcopy(spec)
        s.mix["arrivals"]["rate_per_s"] = rate
        r = cell.execute(s, seed=args.seed, seconds=args.seconds, trace=False,
                         devices=devices[:s.chips], peak=peak,
                         t_process=time.perf_counter(),
                         warm_marker=run.CACHE_DIR / "bench" / f"{s.name}.warm",
                         say=lambda m: print(m, file=sys.stderr, flush=True),
                         records=records)
        rec = records[-1]
        w = rec.window
        due = sorted((sp for sp in w.specs if 0 <= sp.arrival < w.seconds),
                     key=lambda sp: sp.arrival)
        t = readings.ttfts(rec)
        third = max(len(due) // 3, 1)
        late = [rec.tokens.times.get(sp.uid, [np.inf])[0] >= w.end
                for sp in due]
        print(json.dumps({
            "rate_per_s": rate, "due": len(due),
            "no_first_token_in_window": int(sum(late)),
            "tokens_per_s": r["metrics"]["tokens_per_s"]["value"],
            "itl_p95_ms": r["metrics"]["itl_p95_ms"]["value"],
            "ttft_p50_ms": readings.percentile(t, 50) * 1e3,
            "ttft_p95_ms": readings.percentile(t, 95) * 1e3,
            "ttft_last_over_first_third": float(
                np.median(t[-third:]) / max(np.median(t[:third]), 1e-9)),
            "correct": r["correct"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
