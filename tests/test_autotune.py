"""kernels.autotune: candidate pruning, cache round-trip, tuned dispatch."""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.autotune import (AutotuneCache, CACHE_VERSION,
                                    KernelConfig, autotune,
                                    candidate_configs, choose_impl,
                                    get_or_tune, VMEM_BUDGET_BYTES)


def _rand(key, shape):
    return jax.random.normal(key, shape, jnp.float32)


# ----------------------------------------------------------------- candidates

@pytest.mark.parametrize("m,k,n", [(8, 16, 8), (64, 200, 40),
                                   (256, 1024, 256), (1000, 4000, 1000)])
def test_candidate_configs_valid(m, k, n):
    cands = candidate_configs(m, k, n)
    assert cands, "pruning must never empty the grid"
    for cfg in cands:
        assert cfg.is_valid()
        assert cfg.bk % cfg.chunk == 0
        assert cfg.vmem_bytes() <= VMEM_BUDGET_BYTES


def test_candidate_configs_prunes_oversized_blocks():
    small = candidate_configs(8, 16, 8)
    assert all(c.bn == 128 and c.bk == 128 for c in small)
    big = candidate_configs(1024, 4096, 1024)
    assert any(c.bk == 512 for c in big)
    # prefill/train-sized M never sees GEMV tiles
    assert all(c.bm >= 128 for c in big)


def test_candidate_configs_skinny_adds_gemv_tiles():
    """Decode-shaped (M ≤ SKINNY_M_MAX) problems get the GEMV-like bm tile
    at the bucket size and no other: M lies on the kernel's lanes, so any
    larger bm (the 128 prefill tile included) only pads."""
    cands = candidate_configs(8, 256, 128)
    assert cands and {c.bm for c in cands} == {8}
    cands33 = candidate_configs(33, 256, 128)
    assert cands33 and {c.bm for c in cands33} == {64}   # bucket_m(33) == 64
    for c in cands + cands33:
        assert c.is_valid()


def test_bucket_m_classes():
    from repro.kernels.autotune import SKINNY_M_MAX, bucket_m
    assert [bucket_m(m) for m in (1, 8, 9, 16, 33, 64)] == [8, 8, 16, 16,
                                                            64, 64]
    assert bucket_m(SKINNY_M_MAX + 1) == SKINNY_M_MAX + 1   # exact above
    # the cache key buckets skinny M: every batch size in a bucket shares
    # one tuned entry; K/N stay exact
    k3 = AutotuneCache.key(3, 256, 128, 8, backend="cpu")
    k8 = AutotuneCache.key(8, 256, 128, 8, backend="cpu")
    k9 = AutotuneCache.key(9, 256, 128, 8, backend="cpu")
    assert k3 == k8 != k9
    assert ":m8:" in k8 and ":m16:" in k9


# ---------------------------------------------------------------------- cache

def test_cache_roundtrip_across_instances(tmp_path):
    path = tmp_path / "tune.json"
    cache = AutotuneCache(path)
    key = cache.key(64, 200, 40, 8, backend="cpu")
    assert cache.get(key) is None
    cfg = KernelConfig(bm=128, bn=128, bk=256, chunk=16)
    cache.put(key, cfg, elapsed_us=123.4)
    assert cache.get(key) == cfg
    # fresh instance re-reads from disk
    reloaded = AutotuneCache(path)
    assert len(reloaded) == 1
    assert reloaded.get(key) == cfg
    doc = json.loads(path.read_text())
    assert doc["version"] == CACHE_VERSION
    assert doc["entries"][key]["us_per_call"] == pytest.approx(123.4)


def test_cache_key_carries_interpret_mode():
    """Interpret-mode sweep timings say nothing about compiled throughput:
    the two modes must occupy disjoint cache keys on the same backend."""
    k_interp = AutotuneCache.key(64, 200, 40, 8, backend="cpu", interpret=True)
    k_comp = AutotuneCache.key(64, 200, 40, 8, backend="cpu", interpret=False)
    assert k_interp != k_comp
    assert ":interp:" in k_interp and ":compiled:" in k_comp
    # default resolves from the active backend (CPU test runner -> interpret)
    assert AutotuneCache.key(64, 200, 40, 8, backend="cpu") == k_interp


@pytest.mark.parametrize("stale_version", [1, 2])
def test_cache_invalidates_stale_documents(tmp_path, stale_version):
    """Older documents must be dropped, not served: v1 keys carried no
    interpret flag, and v2 winners at skinny keys were swept without the
    GEMV-like bm candidates (a hit never re-sweeps, so a stale winner would
    pin decode shapes to the old 128-row tile forever)."""
    path = tmp_path / "tune.json"
    path.write_text(json.dumps({
        "version": stale_version,
        "entries": {"sc_gemm:cpu:interp:m8:k512:n512:b8":
                    {"bm": 128, "bn": 128, "bk": 256, "chunk": 16}}}))
    cache = AutotuneCache(path)
    assert len(cache) == 0
    # first write persists the migrated (empty) current-version document
    cache.put(cache.key(1, 2, 3, 8, backend="cpu"), KernelConfig())
    doc = json.loads(path.read_text())
    assert doc["version"] == CACHE_VERSION and len(doc["entries"]) == 1


def test_cache_tolerates_corrupt_file(tmp_path):
    path = tmp_path / "tune.json"
    path.write_text("{not json")
    cache = AutotuneCache(path)          # must not raise
    assert len(cache) == 0
    cache.put(cache.key(1, 2, 3, 8, backend="cpu"), KernelConfig())
    assert len(AutotuneCache(path)) == 1


def test_cache_concurrent_writers_merge(tmp_path):
    """Two cache instances (≈ two tuner processes) writing different keys
    must both survive on disk: _save merges the on-disk document under its
    own entries before the atomic replace."""
    path = tmp_path / "tune.json"
    c1, c2 = AutotuneCache(path), AutotuneCache(path)   # both loaded empty
    k1 = c1.key(128, 256, 128, 8, backend="cpu")
    k2 = c2.key(256, 512, 256, 8, backend="cpu")
    c1.put(k1, KernelConfig(bk=128))
    c2.put(k2, KernelConfig(bk=256))        # c2 never saw c1's entry
    merged = AutotuneCache(path)
    assert merged.get(k1) == KernelConfig(bk=128)
    assert merged.get(k2) == KernelConfig(bk=256)


def test_cache_concurrent_writer_processes(tmp_path):
    """The real thing, not two in-process instances: two *processes*
    interleave merge-on-save (re-read + update + atomic rename) against one
    JSON cache path. The guarantee under test is exactly what PR 3's logic
    promises — the final rename is a valid (never torn) current-version
    document that contains the last writer's *complete* key set plus every
    sibling key that writer observed. A sibling key racing inside the final
    read→rename window may lose (it just re-tunes); what must be impossible
    is the pre-merge failure mode where one process wipes the *whole*
    sibling set, or a torn/unparseable document."""
    path = tmp_path / "tune.json"
    writer = textwrap.dedent("""
        import sys, time
        from repro.kernels.autotune import AutotuneCache, KernelConfig
        path, tag = sys.argv[1], sys.argv[2]
        cache = AutotuneCache(path)
        for i in range(10):
            cache.put(f"sc_gemm:cpu:interp:m{tag}:k{i}:n1:b8",
                      KernelConfig(bk=128, chunk=8), elapsed_us=1.0 + i)
            time.sleep(0.01)    # interleave with the sibling writer
    """)
    src = str(Path(__file__).resolve().parents[1] / "src")
    procs = [subprocess.Popen(
        [sys.executable, "-c", writer, str(path), tag],
        env={"PYTHONPATH": src, "PATH": "/usr/bin:/bin",
             "JAX_PLATFORMS": "cpu", "HOME": str(tmp_path)})
        for tag in ("a", "b")]
    for p in procs:
        assert p.wait(timeout=120) == 0
    doc = json.loads(path.read_text())           # document never torn
    assert doc["version"] == CACHE_VERSION
    merged = AutotuneCache(path)

    def survivors(tag):
        keys = [f"sc_gemm:cpu:interp:m{tag}:k{i}:n1:b8" for i in range(10)]
        return [k for k in keys if merged.get(k) is not None]

    a, b = survivors("a"), survivors("b")
    # the last writer's own set is complete by construction...
    assert len(a) == 10 or len(b) == 10, (len(a), len(b))
    # ...and merge-on-save preserved the sibling's set too, up to keys still
    # in flight inside the final read→rename window (full overwrite — the
    # bug merge-on-save exists for — would leave exactly 0 of one tag)
    assert len(a) >= 1 and len(b) >= 1, (len(a), len(b))
    assert len(a) + len(b) >= 11
    for key in a + b:                            # no entry ever corrupted
        assert merged.get(key) == KernelConfig(bk=128, chunk=8)


def test_get_or_tune_recovers_from_torn_and_foreign_documents(tmp_path):
    """A torn (truncated mid-write) or foreign (future-versioned) document
    on the cache path degrades to a clean re-tune: the sweep runs, the
    winner is served, and the persisted document is valid again."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(7))
    a, b = _rand(k1, (16, 32)), _rand(k2, (32, 16))
    cands = [KernelConfig(bk=128, chunk=8)]
    for doc in ('{"version": %d, "entries": {"x": {"bm": 12' % CACHE_VERSION,
                json.dumps({"version": CACHE_VERSION + 999,
                            "entries": {"sc_gemm:cpu:interp:m16:k32:n16:b8":
                                        {"bm": 1, "bn": 1, "bk": 1,
                                         "chunk": 1}}})):
        path = tmp_path / "tune.json"
        path.write_text(doc)
        cache = AutotuneCache(path)
        assert len(cache) == 0               # torn/foreign never served
        cfg = get_or_tune(a, b, bits=8, cache=cache, candidates=cands,
                          iters=1)
        assert cfg == cands[0]
        healed = json.loads(path.read_text())
        assert healed["version"] == CACHE_VERSION
        assert len(healed["entries"]) == 1


def test_cache_tolerates_foreign_entries_table(tmp_path):
    """A scribbled-on entries table (wrong types) degrades to re-tuning,
    never a crash."""
    path = tmp_path / "tune.json"
    path.write_text(json.dumps({"version": CACHE_VERSION,
                                "entries": ["not", "a", "map"]}))
    assert len(AutotuneCache(path)) == 0
    path.write_text(json.dumps({"version": CACHE_VERSION,
                                "entries": {"good": {"bm": 128, "bn": 128,
                                                     "bk": 128, "chunk": 8},
                                            "bad": 42}}))
    cache = AutotuneCache(path)
    assert len(cache) == 1 and cache.get("good") == KernelConfig(bk=128, chunk=8)


def test_cache_unwritable_path_degrades_to_memory():
    cache = AutotuneCache("/proc/nonexistent-dir/tune.json")
    key = cache.key(1, 2, 3, 8, backend="cpu")
    cache.put(key, KernelConfig())           # must not raise
    assert cache.get(key) == KernelConfig()  # still served in-memory


def test_cache_rejects_invalid_entry(tmp_path):
    path = tmp_path / "tune.json"
    cache = AutotuneCache(path)
    key = cache.key(4, 4, 4, 8, backend="cpu")
    cache._entries[key] = {"bm": 128, "bn": 128, "bk": 128, "chunk": 3}
    assert cache.get(key) is None        # chunk ∤ bk -> treated as a miss


# ----------------------------------------------------------------- tuned path

def test_get_or_tune_sweeps_then_hits_cache(tmp_path):
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    a, b = _rand(k1, (32, 64)), _rand(k2, (64, 16))
    cache = AutotuneCache(tmp_path / "tune.json")
    cands = [KernelConfig(bk=128, chunk=8), KernelConfig(bk=128, chunk=16)]
    cfg = get_or_tune(a, b, bits=8, cache=cache, candidates=cands, iters=1)
    assert cfg in cands
    assert len(cache) == 1
    # second call must be a pure cache hit (no candidates consulted)
    again = get_or_tune(a, b, bits=8, cache=cache, candidates=[], iters=1)
    assert again == cfg


def test_autotune_returns_best_of_candidates():
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    a, b = _rand(k1, (16, 32)), _rand(k2, (32, 16))
    cands = [KernelConfig(bk=128, chunk=4), KernelConfig(bk=128, chunk=16)]
    cfg, us = autotune(a, b, bits=8, candidates=cands, iters=1)
    assert cfg in cands and us > 0


def test_sc_matmul_pallas_tuned_matches_oracle(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "tune.json"))
    k1, k2 = jax.random.split(jax.random.PRNGKey(2))
    a, b = _rand(k1, (40, 96)), _rand(k2, (96, 24))
    out = ops.sc_matmul_pallas(a, b, bits=8, tune=True)
    expected = ref.sc_matmul_ref(a, b, bits=8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=1e-5, atol=1e-5)
    assert (tmp_path / "tune.json").exists()


def test_choose_impl_cpu_fallback():
    assert jax.default_backend() != "tpu"
    assert choose_impl(512, 512, 512, bits=8) == "mxu_split"
