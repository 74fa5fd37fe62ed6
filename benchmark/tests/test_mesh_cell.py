"""Tests of what the mesh deployment (``gbm500_keyed_mesh4``) brings to
the benchmark: its plain reference, its readers, and its cell run whole
at the rehearsal's tiny size on four CPU devices, once sound and once
with each of two faults planted in the program's keyed shuffle — a
record folded on a chip that does not own it, a cut dispatch that
re-sends its tail — which ``correct`` has to catch. No device number
comes out of these.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python3 -m pytest benchmark/tests -q -p no:cacheprovider
"""

import argparse
import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

from lib import byname  # noqa: E402
from lib.manifest import manifest_faults  # noqa: E402
from reference import shard_ref  # noqa: E402

CELL = "gbm500_keyed_mesh4.kafka_saturated"
# faults of a loaded sandbox, not of the program: the tiny producer
# falls behind when the test run's other workers take the cores
LOAD_FAULTS = ("least_lead_records.harness", "least_lead_records.producer")


def test_owner_is_the_stated_rule():
    chip, row = shard_ref.owner([0, 15250, 15251, 61000], 61001, 4)
    assert chip.tolist() == [0, 0, 1, 3] and row.tolist() == [0, 15250, 0, 15247]
    assert shard_ref.owner([5, 99], 100, 1)[0].tolist() == [0, 0]
    assert shard_ref.tally_by_owner([0, 0, 30, 99, 99, 99], 100, 4) == [2, 1, 0, 3]


def test_the_deployment_states_the_one_chip_guarantees():
    def load(name):
        with open(os.path.join(BENCH, "configs", f"{name}.json")) as fh:
            return json.load(fh)

    one, mesh = load("gbm500_keyed"), load("gbm500_keyed_mesh4")
    assert mesh["must_stay_zero"] == one["must_stay_zero"]
    assert len(mesh["guarantees"]) == len(one["guarantees"])
    assert mesh["model"] == one["model"] and mesh["chips"] == 4
    for k in ("key_col", "width_f32", "decay", "stride"):
        assert mesh["state"][k] == one["state"][k]
    # a chip's piece passes 4 GiB by the table alone; the keys ride the
    # float32 lane; run.py's slack covers a mesh dispatch in flight
    assert mesh["table_slots"] // 4 * 32 >= 4 * 2**30
    assert mesh["key_domain"] <= 0x5F000000 - 0x4B000000
    p = mesh["pipeline"]
    assert (p["in_flight"] + 1) * p["max_dispatch_chunks"] * mesh[
        "compile_batch"] >= 3 * p["queue_capacity"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    # appended after the one-chip cell, its `reduced` its file's, its own
    # metrics listing it alone: the manifest's rules
    assert manifest_faults(manifest) == []


MESH_READERS = (
    "shard_us_per_krec.mesh", "bucket_fill_frac.mesh", "hot_chip_share.mesh",
    "dispatch_cuts_per_kdispatch.mesh", "program_ms_per_dispatch.mesh",
    "chip_busy_spread.mesh", "scoring_program_roofline.mesh",
)


@pytest.mark.parametrize("name", MESH_READERS)
def test_a_reader_finds_nothing_in_a_program_without_the_shuffle(name):
    """The parent's program has no such span or counter: the reader
    returns nothing and does not raise, traced or not."""
    snap = {"counters": {"records_out": 10.0, "batches": 2.0},
            "histograms": {}, "ts": 1.0}
    ctx = {"snap0": snap, "snap1": dict(snap, ts=2.0), "batches": [(1.5, 10)],
           "trace": None, "cfg": {}, "peaks": {}}
    read = byname.load("layer_metrics", name).read
    assert read(ctx) is None
    assert read(dict(ctx, trace={"devices": 0})) is None


def test_readers_on_a_made_up_window():
    c0 = {"mesh_bucket_slots": 0.0, "mesh_bucket_pad_records": 0.0,
          "mesh_dispatch_cuts": 0.0, "batches": 0.0, "records_out": 0.0,
          'mesh_chip_records{chip="0"}': 10.0,
          'mesh_chip_records{chip="1"}': 0.0}
    c1 = {"mesh_bucket_slots": 4000.0, "mesh_bucket_pad_records": 1000.0,
          "mesh_dispatch_cuts": 3.0, "batches": 4.0, "records_out": 3000.0,
          'mesh_chip_records{chip="0"}': 2010.0,
          'mesh_chip_records{chip="1"}': 1000.0}
    hist = {'stage_seconds{stage="shard"}': {"sum": 0.3, "n": 8},
            'stage_seconds{stage="unshard"}': {"sum": 0.1, "n": 4}}
    # two chips; a chip's executions in order of start, chip after chip
    modules = [("jit_state_fn(1)", 0.0, 0.4), ("jit_state_fn(1)", 0.5, 0.4),
               ("jit_state_fn(1)", 0.0, 0.1), ("jit_renorm", 0.2, 0.1),
               ("jit_state_fn(1)", 0.5, 0.1)]
    ctx = {
        "snap0": {"counters": c0, "histograms": {}, "ts": 0.0},
        "snap1": {"counters": c1, "histograms": hist, "ts": 1.0},
        "batches": [(0.5, 3000)],
        "trace": {"devices": 2, "window_s": 1.0, "modules": modules},
        "cfg": {"model_kind": "gbm",
                "model": {"n_trees": 500, "depth": 6, "n_features": 32},
                "state": {"width_f32": 8}},
        "peaks": {"int8_ops": 393e12, "hbm_bytes_per_s": 819e9},
    }

    def read(name):
        return byname.load("layer_metrics", name).read(ctx)

    assert read("bucket_fill_frac.mesh") == pytest.approx(75.0)
    assert read("hot_chip_share.mesh") == pytest.approx(100 * 2000 / 3000)
    assert read("dispatch_cuts_per_kdispatch.mesh") == pytest.approx(750.0)
    assert read("shard_us_per_krec.mesh") == pytest.approx(0.4e6 / 3.0)
    assert read("program_ms_per_dispatch.mesh") == pytest.approx(250.0)
    assert read("chip_busy_spread.mesh") == pytest.approx(100 * (0.8 - 0.3))
    # the hot chip's 500 records of a dispatch against its 0.4 s
    from lib import roofline
    least, _ = roofline.least_seconds(ctx["cfg"], 750 * 2 / 3, ctx["peaks"])
    assert read("scoring_program_roofline.mesh") == pytest.approx(
        100 * least / 0.4)
    assert read("scoring_program_roofline.mesh") < 100.0


def tiny_mesh_run(trace=0):
    import jax

    import rehearse
    import run

    if len(jax.devices()) < 4:
        pytest.skip("the mesh cell needs four devices: set XLA_FLAGS="
                    "--xla_force_host_platform_device_count=4")
    assert CELL in rehearse.cells()
    # a window of one second: these runs share the cores with the rest
    # of tier-1, and what they hold is decided by then
    args = argparse.Namespace(workload=CELL, seed=2**31 + 41, seconds=1.0,
                              trace=trace)
    return run.run_cell(args, overrides=rehearse.TINY, on_chip=False)


def broken(res):
    return sorted(k for k, v in res["compared"].items()
                  if not v["holds"] and k not in LOAD_FAULTS)


def test_the_mesh_cell_runs_whole_on_four_cpu_devices():
    res = tiny_mesh_run()
    assert broken(res) == [] and res["failed"] == 0
    for name in ("state_rows_off_the_stated_rule",
                 "state_chip_records_off_tally", "state_counts_differing",
                 "offsets_lost", "offsets_duplicated",
                 "counter.state_evictions", "compilations_in_window"):
        assert res["compared"][name]["value"] == 0, name
    assert res["device"]["count"] >= 4 and res["metrics"] == {}


def test_a_record_folded_on_the_wrong_chip_is_not_correct(monkeypatch):
    from flink_jpmml_tpu.runtime import shuffle

    real = shuffle._owners

    def owners(table, slots):
        chip, row = real(table, slots)
        return np.where(chip == 1, 2, chip).astype(np.uint8), row

    monkeypatch.setattr(shuffle, "_owners", owners)
    res = tiny_mesh_run()
    assert res["correct"] is False
    c = res["compared"]
    assert c["state_chip_records_off_tally"]["value"] > 0
    assert c["state_counts_differing"]["value"] > 0
    assert c["warmup_score_miss_over_tol"]["holds"]  # the scores are right


def test_a_cut_dispatch_that_resends_its_tail_is_not_correct(monkeypatch):
    from flink_jpmml_tpu.runtime import shuffle

    real = shuffle._Held.move

    def move(self, other, lo, hi):
        real(self, other, max(0, lo - 5) if hi > lo else lo, hi)

    monkeypatch.setattr(shuffle._Held, "move", move)
    try:
        res = tiny_mesh_run()
    except SystemExit:
        return  # the warm-up stream never came whole: the run stopped
    assert res["correct"] is False
    assert (res["failed"] > 0
            or res["compared"]["state_counts_differing"]["value"] > 0)
