"""Record ``data/sc8_engine_two_steps.xplane.pb``: two engine steps of the
``sc8-offline`` cell on one TPU v5e (32 live slots decoding while a 33rd
prompt is prefilled, 128 rows a step), traced with the harness's profiler
settings and ``bench.step`` spans, so that the ``engine.*`` spans sit in the
trace beside the device's operations.

    python3 bench/tests/record_engine_trace.py <output .xplane.pb>
"""
from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(out: str) -> int:
    from bench import cell, run

    spec = cell.load(ROOT, "sc8-offline")
    run.use_checkout_cache()
    import jax
    import numpy as np

    from repro.launch.serve import build_engine
    from repro.serving import Request

    if jax.devices()[0].platform != "tpu":
        print("record_engine_trace: needs a TPU", file=sys.stderr)
        return 2
    ref = cell.reference_module(spec.config)
    sizes = ref.Sizes(spec.config["model"], spec.config["derived"])
    cfg, args = cell.program_config(spec.config, spec.mix)
    engine = build_engine(cfg, ref.make_params(
        sizes, 1, spec.config["model"]["torch_dtype"]), args)
    rng = np.random.default_rng(1)
    for i in range(engine.capacity + 2):
        engine.submit(Request(uid=f"r{i}", max_new_tokens=256,
                              prompt=rng.integers(0, sizes.vocab, 512)))
    while len(engine.pool.entries) < engine.capacity:
        engine.step()
    for _ in range(2):          # the next prompt's first chunks compile
        engine.step()
    trace_dir = tempfile.mkdtemp(prefix="engine-trace-")
    start, stop = cell.profiler(trace_dir)
    start()
    for _ in range(2):
        with jax.profiler.TraceAnnotation("bench.step"):
            engine.step()
    stop()
    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    shutil.copy(path, out)
    print(f"{out}: {os.path.getsize(out)} bytes")
    return 0


if __name__ == "__main__":
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    sys.exit(main(sys.argv[1]))
