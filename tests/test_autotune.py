"""Autotune cache + warmup sweep (compile/autotune.py).

Pins the satellite/acceptance behaviors of ISSUE 2: the sweep measures
fused-vs-host encode (and Pallas tile shapes) and applies the winner;
the winning config round-trips through the on-disk JSON cache and is
consulted by ``build_quantized_scorer`` on the next compile; a corrupt
cache file reads as empty (silent re-tune, never a crash); stale
configs the current build can't honour degrade to defaults."""

import json

import numpy as np
import pytest

from assets.generate import gen_gbm
from flink_jpmml_tpu.compile import autotune
from flink_jpmml_tpu.compile.qtrees import build_quantized_scorer
from flink_jpmml_tpu.pmml import parse_pmml_file


@pytest.fixture
def doc(tmp_path):
    return parse_pmml_file(
        gen_gbm(str(tmp_path), n_trees=10, depth=3, n_features=4)
    )


def _X(n=64, f=4, seed=0):
    rng = np.random.default_rng(seed)
    return rng.normal(0.0, 1.5, size=(n, f)).astype(np.float32)


class TestSweep:
    def test_sweep_measures_both_encodes(self, doc):
        q = build_quantized_scorer(doc, batch_size=64)
        cfg = autotune.sweep(q, _X(), repeats=1)
        assert cfg.source == "sweep"
        assert {"encode_host", "encode_fused"} <= set(cfg.rates)
        assert cfg.encode in ("host", "fused")
        assert q.encode_mode == cfg.encode
        assert q.tuned is cfg

    def test_pallas_tile_sweep_keeps_parity(self, doc):
        qp = build_quantized_scorer(
            doc, batch_size=64, backend="pallas", pallas_interpret=True
        )
        qx = build_quantized_scorer(doc, batch_size=64, backend="xla")
        cfg = autotune.sweep(qp, _X(), repeats=1)
        assert any(k.startswith("pallas_b") for k in cfg.rates)
        # whatever tile won, scoring is still byte-exact vs the XLA path
        X = _X(128, seed=1)
        Xq = qp.wire.encode(X)
        np.testing.assert_allclose(
            np.asarray(qp.predict_wire(Xq), np.float32),
            np.asarray(qx.predict_wire(Xq), np.float32),
            rtol=1e-5, atol=1e-6,
        )

    def test_sample_tiled_to_batch(self, doc):
        # a sample smaller than the compile batch must not crash the
        # sweep (it is tiled up to one full dispatch)
        q = build_quantized_scorer(doc, batch_size=64)
        cfg = autotune.sweep(q, _X(10), repeats=1)
        assert cfg.rec_s and cfg.rec_s > 0


class TestCacheRoundTrip:
    def test_ensure_tuned_persists_and_next_build_consults(self, doc):
        q = build_quantized_scorer(doc, batch_size=64)
        cfg = autotune.ensure_tuned(q, _X(), repeats=1)
        path = autotune.cache_path()
        data = json.load(open(path))
        assert data["version"] == 1 and data["entries"]
        # a fresh compile of the same model picks the config up from
        # disk (source "cache") without re-sweeping
        q2 = build_quantized_scorer(doc, batch_size=64)
        assert q2.tuned is not None and q2.tuned.source == "cache"
        assert q2.encode_mode == cfg.encode

    def test_cache_hit_applies_without_sweep(self, doc):
        q = build_quantized_scorer(doc, batch_size=64)
        autotune.store(
            q.model_hash, autotune.backend_key(q),
            autotune.TunedConfig(encode="fused", source="sweep"),
        )
        cfg = autotune.ensure_tuned(q, _X(), repeats=1)
        assert cfg.source == "cache"
        assert q.encode_mode == "fused"

    def test_disable_env_bypasses_cache(self, doc, monkeypatch):
        # the bench's --no-autotune ablation: a cached config must NOT
        # be applied at compile when FJT_AUTOTUNE_DISABLE is set
        q = build_quantized_scorer(doc, batch_size=64)
        autotune.store(
            q.model_hash, autotune.backend_key(q),
            autotune.TunedConfig(encode="fused", source="sweep"),
        )
        monkeypatch.setenv("FJT_AUTOTUNE_DISABLE", "1")
        q2 = build_quantized_scorer(doc, batch_size=64)
        assert q2.tuned is None and q2.encode_mode == "host"

    def test_apply_releases_rebuild_hook(self, doc):
        # tuned once: the pallas rebuild closure (pinning host packing
        # tables) must be released after the config is applied
        qp = build_quantized_scorer(
            doc, batch_size=64, backend="pallas", pallas_interpret=True
        )
        assert qp._pallas_rebuild is not None
        autotune.apply(qp, autotune.TunedConfig(encode="host"))
        assert qp._pallas_rebuild is None

    def test_distinct_backend_keys_do_not_collide(self, doc):
        q = build_quantized_scorer(doc, batch_size=64)
        autotune.store(
            q.model_hash, "tpu:v5_lite:pallas",
            autotune.TunedConfig(encode="fused", source="sweep"),
        )
        # same model, DIFFERENT backend key: no entry for this one
        assert autotune.lookup(q.model_hash, autotune.backend_key(q)) is None

    def test_pallas_layout_config_rebuilds_from_cache(self, doc):
        qp = build_quantized_scorer(
            doc, batch_size=64, backend="pallas", pallas_interpret=True
        )
        autotune.store(
            qp.model_hash, autotune.backend_key(qp),
            autotune.TunedConfig(
                encode="host", layout="mega_bfs", source="sweep"
            ),
        )
        qp2 = build_quantized_scorer(
            doc, batch_size=64, backend="pallas", pallas_interpret=True
        )
        assert qp2.tuned is not None and qp2.layout == "mega_bfs"
        X = _X(seed=2)
        Xq = qp2.wire.encode(X)
        # every catalogue layout is byte-identical to the built default
        np.testing.assert_array_equal(
            np.asarray(qp2.predict_wire(Xq), np.float32),
            np.asarray(qp.predict_wire(Xq), np.float32),
        )


class TestCorruptCache:
    def test_corrupt_file_reads_empty_and_retunes(self, doc):
        path = autotune.cache_path()
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text("{definitely not json]]")
        q = build_quantized_scorer(doc, batch_size=64)  # no crash
        assert q.tuned is None
        assert autotune.lookup(q.model_hash, autotune.backend_key(q)) is None
        cfg = autotune.ensure_tuned(q, _X(), repeats=1)
        assert cfg.source == "sweep"  # silently re-tuned
        # and the rewrite left a valid file behind
        assert json.load(open(path))["entries"]

    def test_wrong_schema_reads_empty(self, doc):
        path = autotune.cache_path()
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps({"version": 1, "entries": [1, 2, 3]}))
        q = build_quantized_scorer(doc, batch_size=64)
        assert autotune.lookup(q.model_hash, autotune.backend_key(q)) is None

    def test_garbage_entry_values_tolerated(self, doc):
        q = build_quantized_scorer(doc, batch_size=64)
        path = autotune.cache_path()
        path.parent.mkdir(parents=True, exist_ok=True)
        key = f"{q.model_hash}|{autotune.backend_key(q)}"
        path.write_text(json.dumps({
            "version": 1,
            "entries": {key: {"encode": 7, "block_b": "wat", "gt": None}},
        }))
        # a malformed entry must not break the compile-time consult
        q2 = build_quantized_scorer(doc, batch_size=64)
        assert q2.encode_mode in ("host", "fused")


class TestApply:
    def test_stale_fused_degrades_to_host(self, doc):
        q = build_quantized_scorer(doc, batch_size=64)
        q._fused_inner = None  # model without device tables
        autotune.apply(q, autotune.TunedConfig(encode="fused"))
        assert q.encode_mode == "host"

    def test_clear_scoped_and_full(self, doc):
        q = build_quantized_scorer(doc, batch_size=64)
        key = autotune.backend_key(q)
        autotune.store(q.model_hash, key, autotune.TunedConfig())
        autotune.store("deadbeef", key, autotune.TunedConfig())
        autotune.clear(q.model_hash)
        assert autotune.lookup(q.model_hash, key) is None
        assert autotune.lookup("deadbeef", key) is not None
        autotune.clear()
        assert autotune.lookup("deadbeef", key) is None
