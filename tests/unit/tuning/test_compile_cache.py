"""Compile-cache lifecycle tests: the location rule, the hit/miss counter bridge,
the manifest round-trip + toolchain verification, and the ``warm()`` pre-compile
pass."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache as jax_cache

from nanofed_tpu.models import get_model
from nanofed_tpu.observability.registry import MetricsRegistry
from nanofed_tpu.trainer import TrainingConfig
from nanofed_tpu.tuning import (
    PopulationSpec,
    TuningSpace,
    build_manifest,
    verify_manifest,
    warm,
    write_manifest,
)
from nanofed_tpu.tuning import compile_cache
from nanofed_tpu.utils.platform import (
    compilation_cache_dir,
    enable_compilation_cache,
)
from nanofed_tpu.tuning.compile_cache import (
    COMPILE_CACHE_HITS,
    COMPILE_CACHE_MISSES,
    install_compile_cache_metrics,
)

MODEL = get_model("digits_mlp")
POP = PopulationSpec(num_clients=8, capacity=32, sample_shape=(8, 8, 1))
TRAINING = TrainingConfig(batch_size=16, local_epochs=1, learning_rate=0.1)
ONE_CAND_SPACE = TuningSpace(
    client_chunks=(None,), rounds_per_blocks=(1,), model_shards=(1,),
    batch_sizes=(16,),
)

REPO = Path(__file__).resolve().parents[3]


@pytest.fixture
def cache_env(tmp_path, monkeypatch):
    """A private cache directory placed the way an operator places one: through
    ``JAX_COMPILATION_CACHE_DIR``.  jax binds that variable at import, so the
    fixture sets the config value it would have produced, and un-latches the
    cache object around the test (earlier tests compiled under another dir)."""
    cache = tmp_path / "cache"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(cache))
    before = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_enable_compilation_cache", True)  # conftest turns it off
    jax_cache.reset_cache()
    yield cache
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_compilation_cache_dir", before)
    jax_cache.reset_cache()


class TestLocationRule:
    def test_env_dir_wins_and_config_is_left_alone(self, tmp_path, monkeypatch):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "outside"))
        updates = []
        monkeypatch.setattr(
            jax.config, "update", lambda *a, **k: updates.append(a)
        )
        assert enable_compilation_cache() == str(tmp_path / "outside")
        assert updates == []
        assert not (tmp_path / "outside").exists()

    def test_default_is_the_checkout_whatever_the_cwd(self, tmp_path, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        updates = []
        monkeypatch.setattr(
            jax.config, "update", lambda *a, **k: updates.append(a)
        )
        seen = []
        for cwd in (tmp_path, REPO / "tests"):
            monkeypatch.chdir(cwd)
            assert compilation_cache_dir() == str(REPO / ".jax_cache")
            seen.append(enable_compilation_cache())
        assert seen == [str(REPO / ".jax_cache")] * 2
        assert ("jax_compilation_cache_dir", str(REPO / ".jax_cache")) in updates


# jax.monitoring keeps listeners forever, so the FIRST install in the process
# wins the registry (another test in the same pytest run — e.g. warm() — may
# have already installed with the default registry); read the counters from
# whichever registry the bridge actually adopted.
REGISTRY = MetricsRegistry()


def adopted_registry() -> MetricsRegistry:
    assert install_compile_cache_metrics(REGISTRY) is True
    return compile_cache._metrics_registry


class TestCounterBridge:
    def test_install_is_idempotent(self):
        reg = adopted_registry()
        assert install_compile_cache_metrics(MetricsRegistry()) is True
        # Later registries are NOT adopted (first-caller rule) — the counters
        # live in the first caller's registry and nowhere else.
        assert COMPILE_CACHE_HITS in reg.snapshot()

    def test_miss_then_hit_counted(self, cache_env):
        REGISTRY = adopted_registry()
        min_secs = jax.config.jax_persistent_cache_min_compile_time_secs
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        try:
            def misses():
                snap = REGISTRY.snapshot()
                return snap[COMPILE_CACHE_MISSES]["values"].get("", 0)

            def hits():
                snap = REGISTRY.snapshot()
                return snap[COMPILE_CACHE_HITS]["values"].get("", 0)

            m0, h0 = misses(), hits()
            x = jnp.ones((16, 16))
            jax.jit(lambda a: jnp.tanh(a) @ a.T)(x).block_until_ready()
            # XLA emits one miss event per cached module part, so assert
            # direction, not an exact count.
            m1, h1 = misses(), hits()
            assert m1 > m0 and h1 == h0
            # A DISTINCT jit of the same jaxpr replays from the persistent
            # cache: hits, no new miss.
            jax.jit(lambda a: jnp.tanh(a) @ a.T)(x).block_until_ready()
            assert hits() > h1 and misses() == m1
        finally:
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", min_secs
            )


class TestManifest:
    def test_build_and_write_round_trip(self, tmp_path):
        (tmp_path / "xla_entry_0").write_bytes(b"\x00" * 64)
        (tmp_path / "autotune_deadbeef.json").write_text(json.dumps(
            {"cache_key": "deadbeef" * 8, "winner": {"rounds_per_block": 2}}
        ))
        path = write_manifest(tmp_path)
        d = json.loads(path.read_text())
        assert d["xla_entries"] == 1 and d["xla_bytes"] == 64
        assert d["autotune_entries"][0]["cache_key"] == ("deadbeef" * 8)[:16]
        assert d["autotune_entries"][0]["winner"] == {"rounds_per_block": 2}
        assert d["toolchain"]["jax"] == str(jax.__version__)
        # Re-building does not count the manifest itself as an entry.
        assert build_manifest(tmp_path)["xla_entries"] == 1

    def test_verify_matching_toolchain(self, tmp_path):
        write_manifest(tmp_path)
        v = verify_manifest(tmp_path)
        assert v["compatible"] is True and v["reasons"] == []

    def test_verify_flags_foreign_jaxlib(self, tmp_path, monkeypatch):
        write_manifest(tmp_path)
        import jaxlib

        monkeypatch.setattr(jaxlib, "__version__", "0.0.0-foreign", raising=False)
        v = verify_manifest(tmp_path)
        assert v["compatible"] is False
        assert any("jaxlib" in r for r in v["reasons"])

    def test_verify_missing_manifest_is_stated_not_raised(self, tmp_path):
        v = verify_manifest(tmp_path / "nowhere")
        assert v["compatible"] is False
        assert any("no manifest" in r for r in v["reasons"])
        assert v["manifest"] is None


class TestWarm:
    def test_warm_compiles_and_stamps_manifest(self, cache_env):
        cache = cache_env
        result = warm(MODEL, POP, TRAINING, num_rounds=2, space=ONE_CAND_SPACE)
        assert result.autotune.compiles == 1
        assert result.programs[0]["program"].startswith("cand_")
        assert result.programs[0]["compile_seconds"] > 0
        d = json.loads((cache / "manifest.json").read_text())
        assert d["warmed"]["compiles"] == 1
        assert d["warmed"]["model"] == MODEL.name
        assert d["warmed"]["cache_key"] == result.autotune.cache_key[:16]
        # The sweep table itself shipped into the cache dir.
        assert d["autotune_entries"]
        assert verify_manifest(cache)["compatible"] is True

    def test_rewarm_hits_the_autotune_cache(self, cache_env):
        cache = cache_env
        warm(MODEL, POP, TRAINING, num_rounds=2, space=ONE_CAND_SPACE)
        again = warm(MODEL, POP, TRAINING, num_rounds=2, space=ONE_CAND_SPACE)
        assert again.autotune.cache_hit is True
        assert again.autotune.compiles == 0
        assert again.programs == []
        manifest = json.loads((cache / "manifest.json").read_text())
        assert manifest["warmed"]["cache_hit"] is True

    def test_warm_emits_compile_records(self, cache_env):
        class FakeTelemetry:
            def __init__(self):
                self.records = []

            def record(self, rtype, **fields):
                self.records.append({"type": rtype, **fields})

        tel = FakeTelemetry()
        warm(
            MODEL, POP, TRAINING, num_rounds=2, space=ONE_CAND_SPACE,
            telemetry=tel, force=True,
        )
        assert [r for r in tel.records if r["type"] == "compile"]
