"""chip_smoke.py: its phase functions at reduced size on the CPU, with the
checks ``main`` gates ``ok`` on, and ``main``'s refusal to run without a
TPU (it has no CPU branch)."""
import logging
import os

import jax
import pytest

import chip_smoke

#: The chip workload's flags cut to a CPU-sized reduced model.
REDUCED_FLAGS = ("--arch", "smollm-360m", "--reduced", "--requests", "4",
                 "--capacity", "2", "--prompt-len", "32", "--gen", "8",
                 "--block", "8", "--chunk", "8")


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Every phase of the chip workload, served at reduced size on one
    model and one set of params, with each phase's dispatch notes."""
    mp = pytest.MonkeyPatch()
    mp.setenv("REPRO_AUTOTUNE_CACHE",
              str(tmp_path_factory.mktemp("tune") / "tune.json"))
    log = logging.getLogger("repro.dispatch")
    level = log.level
    # setLevel, not the attribute: it also drops the logger's cached
    # "INFO is off" answer from dispatches traced by earlier modules
    log.setLevel(logging.INFO)
    args = chip_smoke.build_parser().parse_args(list(REDUCED_FLAGS))
    cfg = chip_smoke.build_config(args)
    params = chip_smoke.bind(cfg).init_params(jax.random.PRNGKey(0))
    clock = chip_smoke.CompileClock()
    phases = {}
    for name, extra in chip_smoke.PHASES:
        notes = chip_smoke.DispatchNotes()
        log.addHandler(notes)
        phases[name] = chip_smoke.run_phase(REDUCED_FLAGS + extra, params,
                                            clock)
        log.removeHandler(notes)
        phases[name]["notes"] = notes.notes
    yield cfg, params, phases
    log.setLevel(level)
    mp.undo()


@pytest.mark.parametrize("name", [name for name, _ in chip_smoke.PHASES])
def test_phase_drains_every_request(served, name):
    _, _, phases = served
    ph = phases[name]
    assert ph["problems"] == []
    assert len(ph["results"]) == 4
    assert ph["steady_compiles"] == 0      # the fresh engine reused the steps
    if name == "speculative":
        assert ph["stats"]["speculative"]
        assert chip_smoke.stream_agreement(phases["exact"], ph) == (4, 4)


def test_phases_note_every_dispatch(served):
    """Each attention site and SC-GEMM shape a phase traced left a note of
    its path and why; off the TPU, prefill attention and paged decode take
    the jnp/gather paths and SC-GEMM the XLA split."""
    _, _, phases = served
    exact = phases["exact"]["notes"]
    assert any(s.startswith("flash") and p.startswith("jnp: ")
               for s, p in exact.items())
    assert any(s.startswith("paged") and p.startswith("gather: ")
               for s, p in exact.items())
    assert not any(s.startswith("sc_matmul") for s in exact)
    sc = {p for s, p in phases["sc-gemm"]["notes"].items()
          if s.startswith("sc_matmul")}
    assert sc == {"mxu_split"}


def test_check_drained_flags_short_and_foreign_streams(served):
    cfg, _, phases = served
    ph = phases["exact"]
    res = ph["results"][0]
    short = type(res)(**{**res.__dict__, "tokens": res.tokens[:-1]})
    foreign = type(res)(**{**res.__dict__,
                           "tokens": res.tokens * 0 + cfg.vocab_size})
    problems = chip_smoke.check_drained(
        cfg, ph["requests"][:2], [short, foreign], ph["stats"])
    assert any("budget" in p for p in problems)
    assert any("vocabulary" in p for p in problems)
    bad_stats = {**ph["stats"], "nonfinite_logit_rows": 1}
    assert chip_smoke.check_drained(cfg, ph["requests"], ph["results"],
                                    bad_stats)


def test_exact_phase_matches_reference_and_generate(served):
    _, params, phases = served
    assert chip_smoke.reference_error(phases["exact"],
                                      params) <= chip_smoke.REF_TOL
    same, rate = chip_smoke.generate_agreement(phases["exact"], params)
    assert (same, rate) == (chip_smoke.AGREEMENT_REQUESTS, 1.0)


def test_sc_gemm_exactness_at_projection_shapes(served, monkeypatch,
                                                tmp_path):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "tune.json"))
    cfg, _, _ = served
    rows = chip_smoke.sc_gemm_exactness(cfg, [(8, 8), (4, 8)])
    assert len(rows) == 2 * len(chip_smoke.projection_shapes(cfg))
    assert all(r["equal"] for r in rows)


@pytest.fixture
def undo_cache_setup(monkeypatch):
    """Undo what ``setup_compile_cache`` sets: the environment through
    ``monkeypatch``, JAX's cache settings by hand."""
    from jax.experimental.compilation_cache import compilation_cache

    from repro.kernels.autotune import CACHE_ENV

    for var in (CACHE_ENV, "TPU_LOG_DIR"):
        monkeypatch.delenv(var, raising=False)
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in names}
    yield saved
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_location(monkeypatch, tmp_path, undo_cache_setup,
                                from_env):
    """``$JAX_COMPILATION_CACHE_DIR`` is left to JAX; without it the cache
    sits at the git-ignored ``<repo>/.jax_cache``. The autotune JSON and
    the TPU runtime's logs go inside it either way."""
    from repro.kernels.autotune import CACHE_ENV
    from repro.launch import REPO_ROOT, setup_compile_cache

    if from_env:
        # a directory that does not exist yet, two levels deep
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "a/b"))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = setup_compile_cache()
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    if from_env:
        assert got == str(tmp_path / "a/b")
        assert (jax.config.jax_compilation_cache_dir
                == undo_cache_setup["jax_compilation_cache_dir"])
    else:
        assert got == str(REPO_ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
        assert ".jax_cache/" in (REPO_ROOT / ".gitignore").read_text()
    assert os.environ[CACHE_ENV] == os.path.join(got, "sc_gemm_autotune.json")
    assert os.environ["TPU_LOG_DIR"] == os.path.join(got, "tpu_logs")
    assert os.path.isdir(os.environ["TPU_LOG_DIR"])


def test_main_refuses_without_a_tpu(capsys, undo_cache_setup):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main() != 0
    assert '"ok"' not in capsys.readouterr().out
