"""Learned kernel cost model + predict-then-verify search (ISSUE 11).

Pins: the ridge fit recovers a planted cost law and ranks candidates
by it; persistence is atomic and corrupt-tolerant; the search times at
most top-K of the candidate space and feeds the ledger per-variant
feature rows; a cached winner from an older search space reads as no
entry; concurrent ledger writers merge instead of clobbering; and the
live profiler's drift band invalidates a stale prediction (clears the
autotune entry, bumps the cost-model generation, sets
``kernel_pred_error``)."""

import json
import math
import os

import numpy as np
import pytest

from assets.generate import gen_gbm
from flink_jpmml_tpu.compile import autotune, costmodel, layouts
from flink_jpmml_tpu.compile.qtrees import build_quantized_scorer
from flink_jpmml_tpu.obs import profiler
from flink_jpmml_tpu.obs import recorder as flight
from flink_jpmml_tpu.pmml import parse_pmml_file
from flink_jpmml_tpu.utils.metrics import MetricsRegistry


@pytest.fixture
def doc(tmp_path):
    return parse_pmml_file(
        gen_gbm(str(tmp_path), n_trees=10, depth=3, n_features=4)
    )


def _X(n=64, f=4, seed=0):
    rng = np.random.default_rng(seed)
    return rng.normal(0.0, 1.5, size=(n, f)).astype(np.float32)


def _planted_rows(n=40, seed=0):
    """Synthetic (features, y) with y = exp(0.5·a − 0.3·b + c)."""
    rng = np.random.default_rng(seed)
    rows = []
    for _ in range(n):
        a, b, c = rng.normal(size=3)
        rows.append((
            {"a": a, "b": b, "c": c},
            math.exp(0.5 * a - 0.3 * b + c),
        ))
    return rows


class TestCostModel:
    def test_fit_recovers_planted_law(self):
        m = costmodel.CostModel.fit(_planted_rows(), l2=1e-6)
        assert m is not None and m.stats["rows"] == 40
        assert m.stats["r2"] > 0.99
        for f, y in _planted_rows(8, seed=1):
            pred = m.predict(f)
            assert pred is not None
            assert 0.8 < pred / y < 1.25  # within ~±25% out of sample

    def test_rank_orders_by_predicted_cost(self):
        m = costmodel.CostModel.fit(_planted_rows(), l2=1e-6)
        cands = {
            "cheap": {"a": -2.0, "b": 2.0, "c": -1.0},
            "mid": {"a": 0.0, "b": 0.0, "c": 0.0},
            "dear": {"a": 2.0, "b": -2.0, "c": 1.0},
        }
        assert [n for n, _ in m.rank(cands)] == ["cheap", "mid", "dear"]

    def test_fit_skips_garbage_rows(self):
        rows = _planted_rows(10) + [
            ({}, 1.0), (None, 1.0), ({"a": 1.0}, -1.0),
            ({"a": 1.0}, float("nan")), ({"a": 1.0}, "wat"),
        ]
        m = costmodel.CostModel.fit(rows)
        assert m is not None and m.stats["rows"] == 10

    def test_persistence_roundtrip_and_corrupt_tolerance(self, tmp_path):
        path = str(tmp_path / "cm.json")
        m = costmodel.CostModel.fit(_planted_rows())
        costmodel.save(m, path)
        m2 = costmodel.load(path)
        assert m2 is not None
        f = {"a": 0.3, "b": -0.2, "c": 0.1}
        assert m2.predict(f) == pytest.approx(m.predict(f))
        with open(path, "w") as fh:
            fh.write("\x00not json{{{")
        assert costmodel.load(path) is None  # silent refit contract

    def test_persisted_fit_is_platform_scoped(self, tmp_path):
        # a CPU-interpret fit must never rank a TPU search: load()
        # with a platform rejects a file stamped for another one
        path = str(tmp_path / "cm.json")
        costmodel.save(costmodel.CostModel.fit(_planted_rows()), path)
        here = costmodel._current_platform()
        assert costmodel.load(path, platform=here) is not None
        assert costmodel.load(path, platform="not-" + here) is None
        assert costmodel.load(path) is not None  # unscoped: accept

    def test_variant_features_cover_the_search_axes(self, doc):
        q = build_quantized_scorer(doc, batch_size=64)
        feats = costmodel.variant_features(
            costmodel.scorer_meta(q), "pallas", "mega_bfs", wire_bytes=4.0,
        )
        assert feats["layout_mega"] == 1.0 and feats["layout_bfs"] == 1.0
        assert feats["layout_wirepack"] == 0.0
        assert feats["backend_pallas"] == 1.0
        assert feats["log2_wire_bytes"] == 2.0
        assert feats["depth"] == pytest.approx(
            math.log2(q._meta["splits"] + 1)
        )


class TestLedger:
    def test_per_variant_rows_carry_features(self, tmp_path):
        path = str(tmp_path / "kc.json")
        led = profiler.KernelCostLedger(path=path, flush_interval_s=0.0)
        led.update(
            "m1", "pallas", 0.5, 1000, 100.0, 6.0,
            variant="pallas_mega",
            features={"depth": 3.0}, predicted=4e-4,
        )
        entries = profiler.read_ledger(path)
        (key,) = entries
        assert key == "m1|pallas|pallas_mega"
        e = entries[key]
        assert e["features"] == {"depth": 3.0}
        assert e["predicted_s_per_record"] == 4e-4
        assert e["pred_err"] == pytest.approx(0.25)  # |5e-4−4e-4|/4e-4

    def test_concurrent_writers_merge_not_clobber(self, tmp_path):
        # the satellite: two sibling processes flushing must UNION
        # their entries, not last-writer-wins each other away
        path = str(tmp_path / "kc.json")
        a = profiler.KernelCostLedger(path=path, flush_interval_s=math.inf)
        b = profiler.KernelCostLedger(path=path, flush_interval_s=math.inf)
        a.update("m1", "pallas", 0.5, 1000, None, None, variant="v1")
        b.update("m2", "xla", 0.2, 1000, None, None, variant="v2")
        a.flush()
        b.flush()  # b never saw a's entry in memory
        entries = profiler.read_ledger(path)
        assert set(entries) == {"m1|pallas|v1", "m2|xla|v2"}

    def test_same_key_newest_ts_wins(self, tmp_path):
        path = str(tmp_path / "kc.json")
        a = profiler.KernelCostLedger(path=path, flush_interval_s=math.inf)
        b = profiler.KernelCostLedger(path=path, flush_interval_s=math.inf)
        a.update("m", "xla", 0.4, 1000, None, None)
        a.flush()
        b.update("m", "xla", 0.1, 1000, None, None)  # fresher ts
        b.flush()
        a.update("m", "xla", 0.4, 1000, None, None)
        # force a's in-memory ts older than b's on-disk entry
        with a._mu:
            a._entries["m|xla"]["ts"] -= 3600.0
            a._dirty = True
        a.flush()
        e = profiler.read_ledger(path)["m|xla"]
        assert e["device_s_per_record"] == pytest.approx(1e-4)

    def test_corrupt_ledger_reads_empty(self, tmp_path):
        path = str(tmp_path / "kc.json")
        with open(path, "w") as f:
            f.write("{broken")
        assert profiler.read_ledger(path) == {}
        # and a flush over the corrupt file rewrites it valid
        led = profiler.KernelCostLedger(path=path, flush_interval_s=0.0)
        led.update("m", "xla", 0.1, 100, None, None)
        assert json.load(open(path))["entries"]

    def test_fit_from_ledger(self, tmp_path):
        path = str(tmp_path / "kc.json")
        led = profiler.KernelCostLedger(path=path, flush_interval_s=math.inf)
        rng = np.random.default_rng(3)
        for i in range(10):
            a = float(rng.normal())
            led.update(
                "m", "xla", math.exp(a) * 1e-6 * 1000, 1000, None, None,
                variant=f"v{i}", features={"a": a},
            )
        led.flush()
        m = costmodel.fit_from_ledger(path=path, min_rows=5)
        assert m is not None and m.stats["rows"] == 10
        # legacy rows without features don't break the replay
        led.update("legacy", "xla", 0.1, 100, None, None)
        led.flush()
        assert costmodel.fit_from_ledger(path=path, min_rows=5) is not None


class TestSearch:
    def test_top_k_bounds_timing(self, doc):
        q = build_quantized_scorer(
            doc, batch_size=64, backend="pallas", pallas_interpret=True
        )
        cfg = autotune.sweep(q, _X(), repeats=1, top_k=2)
        s = cfg.search
        assert s is not None
        assert s["timed"] <= 2 < s["candidates_total"]
        assert s["space"] == layouts.SPACE_TAG
        # the timed candidates landed in the ledger as training rows
        rows = costmodel.training_rows()
        assert len(rows) >= s["timed"]

    def test_search_over_a_seeded_ledger_is_learned(self, doc):
        # one model's sweep times the 4-layout catalogue: two models'
        # rows (batch is part of the model hash) reach the fit's floor
        for bs in (64, 128):
            q = build_quantized_scorer(
                doc, batch_size=bs, backend="pallas", pallas_interpret=True
            )
            autotune.sweep(q, _X(), repeats=1, top_k=8)
        q2 = build_quantized_scorer(
            doc, batch_size=64, backend="pallas", pallas_interpret=True
        )
        cfg2 = autotune.sweep(q2, _X(), repeats=1, top_k=3)
        assert cfg2.search["mode"] == "learned"
        assert cfg2.search["timed"] <= 3
        assert cfg2.search["predicted"]  # the whole space was ranked
        assert len(cfg2.search["predicted"]) == cfg2.search["candidates_total"]
        # the incumbent default is always among the verified set — a
        # mispredicting fit must never adopt a variant without having
        # measured the default it would replace
        assert "pallas_ref" in cfg2.rates

    def test_disable_env_falls_back_to_legacy(self, doc, monkeypatch):
        monkeypatch.setenv("FJT_KERNEL_SEARCH_DISABLE", "1")
        q = build_quantized_scorer(
            doc, batch_size=64, backend="pallas", pallas_interpret=True
        )
        cfg = autotune.sweep(q, _X(), repeats=1, top_k=8)
        assert cfg.search["mode"] == "legacy"
        # legacy space = the built default alone
        assert cfg.search["candidates_total"] == 1
        assert cfg.layout == "ref"

    def test_stale_space_tag_reads_as_no_entry(self, doc):
        q = build_quantized_scorer(doc, batch_size=64)
        key = autotune.backend_key(q)
        cfg = autotune.TunedConfig(encode="fused", source="sweep")
        cfg.space = "space-v0:pre-layouts"
        autotune.store(q.model_hash, key, cfg)
        assert autotune.lookup(q.model_hash, key) is None
        # a current-space entry round-trips
        autotune.store(q.model_hash, key, autotune.TunedConfig())
        got = autotune.lookup(q.model_hash, key)
        assert got is not None and got.space == layouts.SPACE_TAG

    def test_pre_layout_entry_without_tag_is_stale(self, doc):
        # a cache written by the previous binary (no space field at
        # all) must silently re-search, not pin its winner
        q = build_quantized_scorer(doc, batch_size=64)
        key = autotune.backend_key(q)
        path = autotune.cache_path()
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps({
            "version": 1,
            "entries": {
                f"{q.model_hash}|{key}": {
                    "encode": "fused", "block_b": 512, "gt": 8,
                    "rec_s": 1e6, "rates": {}, "source": "sweep",
                },
            },
        }))
        assert autotune.lookup(q.model_hash, key) is None
        q2 = build_quantized_scorer(doc, batch_size=64)
        assert q2.tuned is None and q2.encode_mode == "host"

    def test_xla_search_covers_layouts(self, doc):
        q = build_quantized_scorer(doc, batch_size=64, backend="xla")
        cfg = autotune.sweep(q, _X(), repeats=1, top_k=4)
        # uint8 wire: ref + bfs only (wirepack has nothing to pack)
        assert cfg.search["candidates_total"] == 2
        assert any(k.startswith("xla_") for k in cfg.rates)
        # whatever won still scores exactly like a fresh reference
        q_ref = build_quantized_scorer(doc, batch_size=64, backend="xla")
        X = _X(seed=9)
        np.testing.assert_array_equal(
            np.asarray(q.predict_wire(q.wire.encode(X)), np.float32),
            np.asarray(q_ref.predict_wire(q_ref.wire.encode(X)), np.float32),
        )


class TestDriftBandInvalidation:
    def _profile(self, q, predicted):
        from flink_jpmml_tpu.obs import attr

        p = attr.dispatch_profile(q, 64)
        p["predicted_s_per_record"] = predicted
        return p

    def test_sustained_drift_reopens_search(self, doc):
        q = build_quantized_scorer(doc, batch_size=64)
        key = autotune.backend_key(q)
        autotune.store(q.model_hash, key, autotune.TunedConfig())
        assert autotune.lookup(q.model_hash, key) is not None
        m = MetricsRegistry()
        prof = profiler.DeviceProfiler(m, interval_s=0.0)
        gen0 = costmodel.generation()
        # observed 64e-6/64 = 1e-6 s/rec vs predicted 1e-8: 100x out
        # of band, three strikes
        for _ in range(3):
            prof.record_sample(64e-6, self._profile(q, 1e-8))
        assert costmodel.generation() == gen0 + 1
        assert autotune.lookup(q.model_hash, key) is None
        assert (
            m.struct_snapshot()["gauges"]["kernel_pred_error"]["value"] > 0
        )
        kinds = [e.get("kind") for e in flight.events()]
        assert "kernel_search_stale" in kinds
        assert "costmodel_stale" in kinds

    def test_in_band_predictions_do_not_invalidate(self, doc):
        q = build_quantized_scorer(doc, batch_size=64)
        key = autotune.backend_key(q)
        autotune.store(q.model_hash, key, autotune.TunedConfig())
        m = MetricsRegistry()
        prof = profiler.DeviceProfiler(m, interval_s=0.0)
        gen0 = costmodel.generation()
        for _ in range(10):
            prof.record_sample(64e-6, self._profile(q, 1.2e-6))
        assert costmodel.generation() == gen0
        assert autotune.lookup(q.model_hash, key) is not None
        err = m.struct_snapshot()["gauges"]["kernel_pred_error"]["value"]
        assert 0 <= err < 0.5

    def test_stale_trigger_is_one_shot_per_prediction(self, doc):
        # a long-lived server with a permanently-out-of-band config
        # must fire ONCE: re-firing every 3 samples would keep wiping
        # the fit/cache a sibling's fresh re-search just wrote
        q = build_quantized_scorer(doc, batch_size=64)
        m = MetricsRegistry()
        prof = profiler.DeviceProfiler(m, interval_s=0.0)
        gen0 = costmodel.generation()
        for _ in range(12):
            prof.record_sample(64e-6, self._profile(q, 1e-8))
        assert costmodel.generation() == gen0 + 1  # exactly one firing
        # a NEW prediction (a re-search ran) re-arms the band
        for _ in range(3):
            prof.record_sample(64e-6, self._profile(q, 2e-8))
        assert costmodel.generation() == gen0 + 2

    def test_degraded_cached_variant_ships_no_prediction(self, doc):
        # a cached variant this build can't honour (wirepack is an
        # XLA-only layout) degrades to the built defaults — and must
        # NOT ship the unapplied variant's id/prediction into the
        # ledger or the live drift band
        from flink_jpmml_tpu.obs import attr

        qp = build_quantized_scorer(
            doc, batch_size=64, backend="pallas", pallas_interpret=True
        )
        autotune.apply(qp, autotune.TunedConfig(
            layout="wirepack", predicted_s_per_record=1e-6, source="sweep",
        ))
        assert qp._pred_s_per_record is None
        p = attr.dispatch_profile(qp, 64)
        assert p["predicted_s_per_record"] is None
        assert p["model_hash"] == qp.model_hash
        assert p["variant"] == "pallas_ref"  # what actually serves
        assert p["features"]["layout_wirepack"] == 0.0

    def test_no_prediction_no_gauge(self, doc):
        q = build_quantized_scorer(doc, batch_size=64)
        m = MetricsRegistry()
        prof = profiler.DeviceProfiler(m, interval_s=0.0)
        from flink_jpmml_tpu.obs import attr

        prof.record_sample(64e-6, attr.dispatch_profile(q, 64))
        assert "kernel_pred_error" not in m.struct_snapshot()["gauges"]
