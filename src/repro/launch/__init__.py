"""Launchers: mesh construction, multi-pod dry-run, train and serve drivers."""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["apply_numeric_overrides", "numeric_overrides",
           "setup_compile_cache"]

#: Checkout root (``src/repro/launch`` is three levels below it).
REPO_ROOT = Path(__file__).resolve().parents[3]


def setup_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``$JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting: it is
    left alone and no other directory is set. Otherwise the cache sits at
    ``<repo root>/.jax_cache`` (git-ignored), a fixed path, so a later
    process of this checkout finds it again. The SC-GEMM autotune cache
    goes into the same directory unless ``$REPRO_AUTOTUNE_CACHE`` names
    another file, so all state a run persists lives in one place. Every
    compile is kept, however short: a serving engine's set-up is dozens of
    small programs, each under JAX's default one-second floor, that add up
    to seconds. The TPU runtime's own log files, which it otherwise
    writes under ``/tmp`` whatever ``$TPU_LOG_DIR=disabled`` says, go to
    ``tpu_logs/`` in the same directory unless ``$TPU_LOG_DIR`` is set.
    Call it from an entry point, never at import, and before the first
    JAX call that starts a backend.
    """
    import jax

    from repro.kernels.autotune import CACHE_ENV

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = str(REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if "TPU_LOG_DIR" not in os.environ:
        # the runtime makes only the last component of a missing path
        os.environ["TPU_LOG_DIR"] = os.path.join(cache_dir, "tpu_logs")
        os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)
    os.environ.setdefault(CACHE_ENV,
                          os.path.join(cache_dir, "sc_gemm_autotune.json"))
    return cache_dir


def numeric_overrides(*, sc_gemm: bool = False,
                      sc_impl: str | None = None) -> dict:
    """--sc-gemm/--sc-impl flags -> ModelConfig override fields. Used by
    :func:`apply_numeric_overrides` (train/serve) and by dryrun, whose
    run_cell takes an overrides dict for its hillclimb-variant interface."""
    overrides = {}
    if sc_gemm:
        overrides["use_sc_gemm"] = True
    if sc_impl is not None:
        overrides["sc_impl"] = sc_impl
    return overrides


def apply_numeric_overrides(cfg, *, sc_gemm: bool = False,
                            sc_impl: str | None = None):
    """Shared --sc-gemm/--sc-impl CLI handling for the launch drivers.

    Returns ``cfg`` with the SC-numeric fields replaced and re-validated (so
    an invalid combination fails identically in train, serve, and dryrun —
    dryrun's run_cell validates after applying its overrides dict).
    """
    import dataclasses
    overrides = numeric_overrides(sc_gemm=sc_gemm, sc_impl=sc_impl)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides).validate()
    return cfg
