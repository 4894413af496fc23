"""Compile the main path's Pallas kernels for a TPU v5e that is described,
not attached: what Mosaic refuses here (an unsupported primitive, a
misaligned slice, a VMEM overrun) never reaches the chip. Shapes are
smollm-360m's (d_model 960, d_ff 2560, vocab 49152, head_dim 64) at a decode
M and the serving benchmark's prefill-chunk M.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.autotune import bucket_m, candidate_configs
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.ops import _sc_matmul_pallas_jit
from repro.kernels.sc_bitops import sc_stream_mul_pallas


@pytest.fixture(scope="module")
def one_chip(tmp_path_factory):
    """One chip of a described v5e:2x2. Its tests compile with 64-bit types
    off, as the chip runs (the suite's conftest turns them on for exact
    error sweeps). The TPU library writes its logs under ``/tmp`` unless
    ``$TPU_LOG_DIR`` names a directory ("disabled" does not stop it)."""
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", str(tmp_path_factory.mktemp("tpu_logs")))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    with jax.enable_x64(False):
        yield SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("m", [8, 128])
@pytest.mark.parametrize("k,n", [(960, 2560), (2560, 960), (960, 49152)])
def test_sc_gemm_kernel_compiles(one_chip, m, k, n):
    """The tuned grid's smallest, default and largest block configurations
    at this shape all lower through Mosaic."""
    cands = candidate_configs(bucket_m(m), k, n)
    picks = {cands[0], cands[len(cands) // 2], cands[-1]}
    a = jax.ShapeDtypeStruct((m, k), jnp.float32, sharding=one_chip)
    b = jax.ShapeDtypeStruct((k, n), jnp.float32, sharding=one_chip)
    for cfg in picks:
        text = _compiled_text(
            lambda x, y: _sc_matmul_pallas_jit(
                x, y, bits=8, bm=cfg.bm, bn=cfg.bn, bk=cfg.bk,
                chunk=cfg.chunk, interpret=False, row_quant=True), a, b)
        assert "tpu_custom_call" in text, cfg


def test_stream_kernel_compiles(one_chip):
    x = jax.ShapeDtypeStruct((64, 128), jnp.int32, sharding=one_chip)
    text = _compiled_text(
        lambda a, b: sc_stream_mul_pallas(a, b, bits=8, block_rows=8), x, x)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("d", [64, 128])
def test_flash_kernel_compiles(one_chip, d):
    q = jax.ShapeDtypeStruct((1, 15, 512, d), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 5, 512, d), jnp.bfloat16, sharding=one_chip)
    text = _compiled_text(
        lambda q, k, v: flash_attention_pallas(q, k, v, causal=True, bq=256,
                                               bk=512),
        q, kv, kv)
    assert "tpu_custom_call" in text


@pytest.fixture(scope="module")
def v5e_mesh(one_chip):
    """A 1x1 serving mesh on the described chip."""
    import numpy as np
    from jax.sharding import Mesh
    return Mesh(np.array([one_chip._device]).reshape(1, 1), ("data", "model"))


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_dsv2lite_offline_steps_compile(v5e_mesh, program):
    """The ``dsv2lite-offline`` cell's step programs at full width
    (deepseek-v2-lite, 8 of 27 layers, all 64 experts, bf16): the decode
    step over 64 slots of 40 pages of 64 tokens, and a 512-row prefill
    chunk into the 2048-token bucket. The routed experts lower to the
    grouped-matmul kernel; the arguments must fit the chip's 16 GB."""
    import dataclasses

    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs.registry import ARCHS
    from repro.launch.steps import (build_chunked_prefill_step,
                                    build_paged_decode_step)
    from repro.models import bind, cache_ops

    cfg = dataclasses.replace(ARCHS["deepseek-v2-lite"], n_layers=8).validate()
    one = NamedSharding(v5e_mesh, P())
    m = bind(cfg)

    def abstract(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)
    if program == "decode":
        step, _, params = build_paged_decode_step(
            cfg, v5e_mesh, capacity=64, block=64, n_blocks=64 * 40,
            max_blocks=40)
        cache = jax.eval_shape(lambda: cache_ops.paged_init(
            m.init_cache, 64, 64 * 40, 64))
        args = (abstract(params), abstract(cache),
                jax.ShapeDtypeStruct((64, 40), jnp.int32, sharding=one),
                {"tokens": jax.ShapeDtypeStruct((64, 1), jnp.int32,
                                                sharding=one)})
    else:
        step, _, params = build_chunked_prefill_step(cfg, v5e_mesh,
                                                     seq_len=2048, chunk=512)
        cache = jax.eval_shape(lambda: m.init_cache(1, 2048))
        args = (abstract(params), abstract(cache),
                {"tokens": jax.ShapeDtypeStruct((1, 512), jnp.int32,
                                                sharding=one),
                 "n_valid": jax.ShapeDtypeStruct((1,), jnp.int32,
                                                 sharding=one)})
    compiled = step.lower(*args).compile()
    mem = compiled.memory_analysis()
    print(f"\ndsv2lite-offline {program}: arguments "
          f"{mem.argument_size_in_bytes / 2**30:.3f} GiB, temporaries "
          f"{mem.temp_size_in_bytes / 2**30:.3f} GiB, outputs "
          f"{mem.output_size_in_bytes / 2**30:.3f} GiB, aliased "
          f"{mem.alias_size_in_bytes / 2**30:.3f} GiB")
    assert "gmm" in compiled.as_text()
    assert mem.argument_size_in_bytes < 16e9
