"""Run one cell of the benchmark once, on the chip this process holds.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are found by name in
``BENCHMARK.json`` at the checkout's root; the configuration's sizes are
``bench/configs/<config>.json``, the mix ``bench/traffic/<traffic>.json``
and each metric's reader ``bench/metrics/<metric>.py``. With ``--trace 0``
the result carries the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, read from a profiler trace of the window.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and with ``--trace 1``
``breakdown``), and last ``compared``: each number the check of ``correct``
compared, with its limit. The same comparisons are the last lines of
standard error. Without a TPU, or with fewer chips than the cell asks
for, the run prints no result and exits with 2.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

#: JAX's persistent compilation cache, the SC-GEMM autotune table and the
#: TPU runtime's logs: one fixed directory inside the checkout, so that only
#: a cell's first run in a checkout compiles, and two checkouts share
#: nothing.
CACHE_DIR = ROOT / ".jax_cache"


def use_checkout_cache() -> str:
    """Point the program's persistent caches at :data:`CACHE_DIR`; call
    before JAX starts a backend. Returns the directory.

    The directory is the checkout's own and holds some tens of MB, so it
    takes no size bound from the environment: under a bound JAX rescans
    the whole directory at every write, and a cold run's 1,300 small
    admission programs then cost over a second each instead of a tenth."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ.pop("JAX_COMPILATION_CACHE_MAX_SIZE", None)
    os.environ.pop("REPRO_AUTOTUNE_CACHE", None)
    os.environ.pop("TPU_LOG_DIR", None)
    import jax
    jax.config.update("jax_compilation_cache_max_size", -1)
    from repro.launch import setup_compile_cache
    return setup_compile_cache()


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    from bench import cell as cellmod
    spec = cellmod.load(ROOT, args.workload)

    cache_dir = use_checkout_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < spec.chips:
        print(f"bench: needs {spec.chips} TPU chip(s); found "
              f"{len(devices)} {devices[0].platform!r} device(s)",
              file=sys.stderr)
        return 2
    return cellmod.run(spec, args, devices[:spec.chips], cache_dir, T_PROCESS,
                       warm_marker=CACHE_DIR / "bench" / f"{spec.name}.warm")


if __name__ == "__main__":
    sys.exit(main())
