"""Kernel registry + autotune cache (DESIGN.md §13): winner persistence,
corrupt-cache fallback, env override, shape bucketing."""
import json

import jax.numpy as jnp
import pytest

from repro.kernels import registry
from repro.kernels.autotune import (AutotuneCache, CACHE_ENV, cache_key,
                                    cached_params, get_cache, reset_cache,
                                    tune)


@pytest.fixture(autouse=True)
def _isolated_cache(tmp_path, monkeypatch):
    """Every test gets its own cache file; the singleton is dropped on
    both sides so no state leaks between tests (or into the kernels'
    normal resolve path used elsewhere in the suite)."""
    monkeypatch.setenv(CACHE_ENV, str(tmp_path / "autotune.json"))
    reset_cache()
    yield
    reset_cache()


def test_registry_lists_all_kernels():
    assert registry.ops() == ["expert_gmm", "flash_attention",
                              "paged_attention", "rmsnorm", "sample_tokens",
                              "sgd_momentum"]
    for name in registry.ops():
        spec = registry.get(name)
        assert set(spec.defaults) == set(spec.tunables)
        assert spec.bench_cases
        # defaults are the first candidate — the sweep always times the
        # untuned baseline, which is what makes speedup >= 1.0 exact
        assert spec.candidates()[0] == spec.defaults


def test_resolve_precedence():
    # no cache entry: defaults
    assert registry.resolve("rmsnorm", {"block_rows": None},
                            "rows=512,d=256,f32") == {"block_rows": 256}
    # cached winner beats defaults
    c = get_cache()
    c.put(cache_key("rmsnorm", "rows=512,d=256,f32"), {"block_rows": 1024},
          tuned_us=1.0, default_us=2.0)
    assert registry.resolve("rmsnorm", {"block_rows": None},
                            "rows=512,d=256,f32") == {"block_rows": 1024}
    # explicit kwarg beats the cached winner
    assert registry.resolve("rmsnorm", {"block_rows": 64},
                            "rows=512,d=256,f32") == {"block_rows": 64}


def test_winner_roundtrip(tmp_path):
    path = tmp_path / "rt.json"
    c = AutotuneCache(path)
    key = cache_key("rmsnorm", "rows=2048,d=512,f32", backend="cpu")
    c.put(key, {"block_rows": 1024}, tuned_us=10.0, default_us=25.0)
    c.save()
    re = AutotuneCache(path)
    assert re.get(key) == {"block_rows": 1024}
    assert re.entries[key]["default_us"] == 25.0
    # unknown key -> None, never a KeyError
    assert re.get("nope|cpu|x") is None


def test_corrupt_cache_warns_and_falls_back(tmp_path, monkeypatch):
    path = tmp_path / "corrupt.json"
    path.write_text("{ this is not json")
    monkeypatch.setenv(CACHE_ENV, str(path))
    reset_cache()
    with pytest.warns(UserWarning, match="falling back to default"):
        c = get_cache()
    assert c.entries == {}
    # resolve still answers with the registered defaults
    assert registry.resolve("rmsnorm", {"block_rows": None},
                            "rows=512,d=256,f32") == {"block_rows": 256}
    # wrong shape (valid json, no entries table) degrades the same way
    path.write_text(json.dumps([1, 2, 3]))
    reset_cache()
    with pytest.warns(UserWarning):
        assert get_cache().entries == {}


def test_env_override_moves_the_cache(tmp_path, monkeypatch):
    a, b = tmp_path / "a.json", tmp_path / "b.json"
    monkeypatch.setenv(CACHE_ENV, str(a))
    reset_cache()
    c = get_cache()
    c.put(cache_key("rmsnorm", "rows=512,d=256,f32"), {"block_rows": 64},
          tuned_us=1.0, default_us=2.0)
    c.save()
    assert a.exists() and not b.exists()
    monkeypatch.setenv(CACHE_ENV, str(b))
    reset_cache()
    assert cached_params("rmsnorm", "rows=512,d=256,f32") is None
    monkeypatch.setenv(CACHE_ENV, str(a))
    reset_cache()
    assert cached_params("rmsnorm",
                         "rows=512,d=256,f32") == {"block_rows": 64}


def test_shape_bucket_collision():
    """Two nearby shapes share one pow2 bucket (and therefore one tuned
    winner); a shape past the next power of two does not."""
    spec = registry.get("rmsnorm")
    w = jnp.zeros((256,))
    b_300 = spec.bucket_of(jnp.zeros((300, 256)), w)
    b_500 = spec.bucket_of(jnp.zeros((500, 256)), w)
    b_600 = spec.bucket_of(jnp.zeros((600, 256)), w)
    assert b_300 == b_500 == "rows=512,d=256,f32"
    assert b_600 == "rows=1024,d=256,f32"
    c = get_cache()
    c.put(cache_key("rmsnorm", b_300), {"block_rows": 1024},
          tuned_us=1.0, default_us=2.0)
    # the collision shape sees the winner, the out-of-bucket one doesn't
    assert registry.resolve("rmsnorm", {"block_rows": None},
                            b_500) == {"block_rows": 1024}
    assert registry.resolve("rmsnorm", {"block_rows": None},
                            b_600) == {"block_rows": 256}
    # last dim is NOT bucketed (it changes the kernel's inner tile), and
    # dtype partitions buckets too
    assert spec.bucket_of(jnp.zeros((300, 192)), w) != b_300
    assert spec.bucket_of(jnp.zeros((300, 256), jnp.bfloat16), w) != b_300


def test_tune_sweeps_and_persists():
    x = jnp.ones((128, 64)) * jnp.arange(64)
    w = jnp.ones((64,))
    rep = tune("rmsnorm", (x, w), repeats=1, warmup=1)
    assert set(rep["params"]) == {"block_rows"}
    assert rep["speedup"] >= 1.0     # defaults are in the sweep
    assert len(rep["sweep"]) == len(registry.get("rmsnorm").candidates())
    # the winner is on disk and consulted by resolve for the SAME bucket
    reset_cache()
    assert cached_params("rmsnorm", rep["bucket"]) == rep["params"]


def test_ops_wrappers_accept_explicit_tunables():
    """The public wrappers keep working with hand-passed schedule kwargs
    (explicit beats cache beats defaults) and produce oracle results."""
    from repro.kernels import ops, ref
    x = jnp.ones((96, 64)) * jnp.arange(64)
    w = jnp.ones((64,))
    want = ref.rmsnorm_ref(x, w)
    for br in (None, 64, 1024):
        got = ops.rmsnorm(x, w, block_rows=br)
        assert jnp.allclose(got, want, atol=1e-5)
