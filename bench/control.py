"""Readings that set a cell's limits on its logit-gap numbers: the
program's over many seeds (the lower readings) and the control's (the
upper), all in one process on the chip.

    python3 bench/control.py --workload <name> --seeds 11,12,13 --seconds 20

The control is the configuration's ``control`` group: the plain reference
one precision step below the configuration's own, put in the program's
place, read at every served position of the same sampled requests (the
gap, under the reference, of the token the control puts first) and judged
by the same comparison as the program. The benchmark's own runs never run
it. The first seed warms the cell as a run does; later seeds reuse its
programs and skip the warm-up. Prints one JSON line per seed.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from bench import cell, run, work
    spec = cell.load(ROOT, args.workload)
    run.use_checkout_cache()
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    peak = work.peaks(devices[0].device_kind)
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        r = cell.execute(spec, seed=seed, seconds=args.seconds, trace=False,
                         devices=devices[:spec.chips], peak=peak,
                         t_process=time.perf_counter(), control=True,
                         warm=i == 0, warm_marker=run.CACHE_DIR / "bench"
                         / f"{spec.name}.warm",
                         say=lambda m: print(m, file=sys.stderr, flush=True))
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "program": r["program"], "control": r["control"],
            "control_numerics": spec.config["control"],
            "checked": r["compared"]["served_tokens_checked"]["value"],
            "metrics": {k: v["value"] for k, v in r["metrics"].items()}}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
