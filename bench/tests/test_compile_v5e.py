"""Each cell's step programs and its reference, compiled at full width for
a described (not attached) TPU v5e chip: what the chip's compiler would
refuse, and the device memory each program needs, at no chip time.

    PYTHONPATH=src:. JAX_PLATFORMS=cpu python -m pytest -q -s \
        bench/tests/test_compile_v5e.py
"""
import json
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from bench import cell, traffic
from bench.reference import llama

ROOT = Path(__file__).resolve().parents[2]
CELLS = [("smollm-360m-sc8", "offline"), ("smollm-360m-bf16", "chat")]
GIB = 2 ** 30


@pytest.fixture(scope="module")
def topo(tmp_path_factory):
    os.environ.setdefault("TPU_LOG_DIR",
                          str(tmp_path_factory.mktemp("tpu_logs")))
    jax.config.update("jax_enable_compilation_cache", False)
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def _load(config, mix):
    conf = json.loads((ROOT / "bench" / "configs" / f"{config}.json")
                      .read_text())
    return conf, traffic.load_mix(mix)


def _abstract(tree, sharding):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                       sharding=sharding),
                        tree)


def _report(name, compiled):
    m = compiled.memory_analysis()
    print(f"\n{name}: arguments {m.argument_size_in_bytes / GIB:.3f} GiB, "
          f"outputs {m.output_size_in_bytes / GIB:.3f} GiB, temporaries "
          f"{m.temp_size_in_bytes / GIB:.3f} GiB, aliased "
          f"{m.alias_size_in_bytes / GIB:.3f} GiB")
    return m


@pytest.mark.parametrize("config,mix", CELLS)
def test_cell_steps_compile_for_v5e(topo, config, mix):
    from repro.launch.steps import (build_chunked_prefill_step,
                                    build_paged_decode_step, prompt_buckets)
    from repro.models import bind, cache_ops
    from repro.serving.slots import PagedSlotPool

    conf, mixd = _load(config, mix)
    cfg, _ = cell.program_config(conf, mixd)
    eng = mixd["engine"]
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    one = NamedSharding(mesh, P())
    block, max_blocks, n_blocks = PagedSlotPool.plan(
        eng["capacity"], eng["max_seq"], eng["block"], None)
    decode, sh, params_abs = build_paged_decode_step(
        cfg, mesh, capacity=eng["capacity"], block=block, n_blocks=n_blocks,
        max_blocks=max_blocks)
    m = bind(cfg)
    data = jax.eval_shape(lambda: cache_ops.paged_init(
        m.init_cache, eng["capacity"], n_blocks, block))
    args = (_abstract(params_abs, one), _abstract(data, one),
            jax.ShapeDtypeStruct((eng["capacity"], max_blocks), jnp.int32,
                                 sharding=one),
            {"tokens": jax.ShapeDtypeStruct((eng["capacity"], 1), jnp.int32,
                                            sharding=one)})
    mem = _report(f"{config} paged decode", decode.lower(*args).compile())
    assert mem.argument_size_in_bytes < 16e9
    top = prompt_buckets(eng["max_seq"], eng["chunk"])[-1]
    step, _, _ = build_chunked_prefill_step(cfg, mesh, seq_len=top,
                                            chunk=eng["chunk"])
    staging = jax.eval_shape(lambda: m.init_cache(1, top))
    batch = {"tokens": jax.ShapeDtypeStruct((1, eng["chunk"]), jnp.int32,
                                            sharding=one),
             "n_valid": jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one)}
    _report(f"{config} chunked prefill, bucket {top}",
            step.lower(_abstract(params_abs, one), _abstract(staging, one),
                       batch).compile())


@pytest.mark.parametrize("config,mix", CELLS)
def test_reference_compiles_for_v5e(topo, config, mix):
    conf, mixd = _load(config, mix)
    one = SingleDeviceSharding(topo.devices[0])
    sizes = llama.Sizes(conf["model"], conf["derived"])
    params = _abstract(jax.eval_shape(lambda: llama.make_params(sizes, 0)),
                       one)
    b, s, r = cell.REF_BLOCK, mixd["engine"]["max_seq"], mixd["output"]["high"]
    sc = conf["numerics"]["projections"] == "sc"
    fn = jax.jit(lambda p, t, rows: llama.logits_at(
        p, t, rows, sizes, numerics="sc" if sc else "exact", bits=8))
    with jax.default_matmul_precision("highest"):
        compiled = fn.lower(
            params, jax.ShapeDtypeStruct((b, s), jnp.int32, sharding=one),
            jax.ShapeDtypeStruct((b, r), jnp.int32, sharding=one)).compile()
    mem = _report(f"{config} reference, {b} x {s} tokens", compiled)
    assert mem.temp_size_in_bytes < 12e9
