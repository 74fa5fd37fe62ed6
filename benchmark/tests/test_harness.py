"""Tests of the benchmark's own code. Not tier-1 (the driver runs
``tests/``; only a benchmark PR may add files, and only here):

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q -p no:cacheprovider

No device number comes out of these: they hold the load generator, the
loaders, the key mix and the comparison that decides ``correct`` to
what ``PERF.md`` says of them, on the CPU at a small size.
"""

import argparse
import dataclasses
import hashlib
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

from lib import byname, loadgen  # noqa: E402
from lib.stream import BLOCK, Stream  # noqa: E402

ZIPF = {"kind": "zipf", "a": 1.1}
INIT = {"n_features": 32, "key_domain": 150_000_000, "key_mix": ZIPF,
        "pool_rows": 16384, "topic": "bench"}
# sha256 of the int64 ranks of blocks 0, 1 and 5, and of rows 1000 to
# 140000, taken on the parent commit (3747a9e) where ``zipf`` was a
# branch of lib/stream.py
PINNED = {
    7: ("388cbc86c7c289a2b83ea393304809d9dc503ca6adb9ab7b83945123c719aea7",
        "d2a19645480ff5c66e08bdb288d46ab526a2a51a3f3330e48b7694d31c61a3d4"),
    2147483909: (
        "be014aa10b953fc6e957d7b1ad9961869ff33860b0d94cf1726cbfd58b737a91",
        "510650a8c0d73473ced6d09e20db9fa7e396b49911bb2915447369af81032782"),
    2**31 + 11: (
        "e73fde5b29f617d3121f74f4d1eac526ca418158472025bdb592d58ed18c7a0d",
        "ba07788ea3ef587774db87296083a562d2878129978ce7c8876e7b530c206691"),
}


def decode_log(broker, n_features):
    from flink_jpmml_tpu.runtime.kafka import decode_record_batches_rows

    segs = list(broker._segs[0])
    assert [a for a, _, _ in segs[1:]] == [b for _, b, _ in segs[:-1]]
    return decode_record_batches_rows(
        b"".join(blob for _, _, blob in segs), n_features)


@pytest.mark.parametrize("encoders,piece", [(1, 512), (4, 8192), (3, 5120)])
def test_log_decodes_back_to_the_stream(monkeypatch, encoders, piece):
    """Whatever the thread count or the piece size, the log holds the
    stream's row at every offset, each offset once, in order, in the
    same segments: a warm-up stretch of an odd length appended at once,
    then the closed-backlog producer against a sink that keeps moving."""
    monkeypatch.setattr(loadgen, "ENCODERS", encoders)
    monkeypatch.setattr(loadgen, "PIECE", piece)
    seed, n_warm, total = 2**31 + 5, 66_536, 66_536 + 4 * 65_536
    gen = loadgen.Generator(dict(INIT, seed=seed))
    try:
        gen.append(0, n_warm)
        gen.start({"loop": "closed_backlog", "chunk_records": 65_536,
                   "backlog_records": 131_072,
                   "producer_max_records_per_s": None}, n_warm)
        deadline = time.monotonic() + 120.0
        while gen.broker.produced < total and time.monotonic() < deadline:
            gen.note_delivered(max(n_warm, gen.broker.produced - 65_536))
            time.sleep(0.002)
        stats = gen.stop()
        assert stats["produced"] >= total
        offs, rows = decode_log(gen.broker, 32)
        assert np.array_equal(offs, np.arange(stats["produced"]))
        want = Stream(seed, 32, INIT["key_domain"], ZIPF, 16384).rows(
            0, stats["produced"])
        assert np.array_equal(rows.view(np.uint32), want.view(np.uint32))
        # segment boundaries do not move with the threads: 512 from the
        # start of each stretch that was appended
        bounds = {a for a, _, _ in gen.broker._segs[0]}
        assert {0, 512, n_warm, n_warm + 512} <= bounds
        assert n_warm - n_warm % 512 in bounds
    finally:
        gen.close()


def test_a_held_producer_keeps_to_its_rate():
    gen = loadgen.Generator(dict(INIT, seed=3))
    try:
        gen.start({"loop": "closed_backlog", "chunk_records": 8192,
                   "backlog_records": 1 << 20,
                   "producer_max_records_per_s": 40_000}, 0)
        time.sleep(1.0)
        stats = gen.stop()
        assert 8192 <= stats["produced"] <= 40_000 * 1.5 + 8192
    finally:
        gen.close()


@pytest.mark.parametrize("seed", sorted(PINNED))
def test_zipf_ranks_are_the_parents(seed):
    s = Stream(seed, 32, 150_000_000, ZIPF, 16384)
    ranks = np.concatenate([s.ranks(b * BLOCK, (b + 1) * BLOCK)
                            for b in (0, 1, 5)])
    got = (hashlib.sha256(ranks.astype("<i8").tobytes()).hexdigest(),
           hashlib.sha256(s.rows(1000, 140_000).tobytes()).hexdigest())
    assert got == PINNED[seed]


def test_blocks_drawn_by_many_threads_are_the_same():
    one = Stream(11, 4, 1000, ZIPF, 64)
    many = Stream(11, 4, 1000, ZIPF, 64)
    want = [one.ranks(b * BLOCK, b * BLOCK + 100) for b in range(6)]
    got = {}

    def draw(b):
        got[b] = many.ranks(b * BLOCK, b * BLOCK + 100)

    threads = [threading.Thread(target=draw, args=(b % 6,)) for b in range(24)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60.0)
        assert not t.is_alive()
    assert all(np.array_equal(got[b], want[b]) for b in range(6))


SECOND = {
    "lib/keymix/roundrobin.py": (
        "import numpy as np\n"
        "def ranks(block, seed, domain, mix, n):\n"
        "    return (np.arange(n, dtype=np.int64) + block * n + seed) % domain\n"
    ),
    "models/constant.py": (
        "SCORE_RTOL, SCORE_ATOL = 0.0, 0.0\n"
        "def generate(seed, model):\n    return float(model['value'])\n"
        "def reference_scores(handle, X):\n"
        "    import numpy as np\n    return np.full(len(X), handle)\n"
    ),
    "warmup_checks/nothing_kept.py": (
        "def check(run):\n    return [], [('state_rows', 0, 0)]\n"
    ),
}


@pytest.fixture
def second_deployment(tmp_path, monkeypatch):
    """A key mix, a model kind and a warm-up check as files of their
    own in a benchmark directory that holds nothing else: what a later
    PR adds, with no edit to run.py, lib/stream.py or lib/loadgen.py."""
    for rel, text in SECOND.items():
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(text)
    monkeypatch.setattr(byname, "BENCH", str(tmp_path))
    return tmp_path


def test_a_second_deployment_is_found_by_name(second_deployment):
    s = Stream(5, 4, 1000, {"kind": "roundrobin"}, 64)
    assert s.ranks(0, 4).tolist() == [5, 6, 7, 8]
    assert s.ranks(BLOCK, BLOCK + 2).tolist() == [(BLOCK + 5) % 1000,
                                                  (BLOCK + 6) % 1000]
    model = byname.load("models", "constant")
    assert model.reference_scores(model.generate(1, {"value": 2.5}),
                                  np.zeros((3, 4))).tolist() == [2.5] * 3
    assert byname.load("warmup_checks", "nothing_kept").check({}) == (
        [], [("state_rows", 0, 0)])
    # the child's generator takes the mix by the same name
    gen = loadgen.Generator(dict(INIT, seed=5, key_mix={"kind": "roundrobin"}))
    try:
        gen.append(0, 1024)
        _, rows = decode_log(gen.broker, 32)
        assert np.array_equal(rows, gen.stream.rows(0, 1024))
    finally:
        gen.close()


@pytest.mark.parametrize("folder,name", [
    ("lib/keymix", "zipf"), ("models", "gbm"),
    ("warmup_checks", "all_resident"), ("paths", "block"),
])
def test_what_the_files_name_is_there(folder, name):
    assert byname.load(folder, name) is not None


@pytest.mark.parametrize("folder,name", [
    ("lib/keymix", "sweep"), ("models", "mlp"), ("warmup_checks", "expiring"),
    ("models", "../run"),
])
def test_an_unknown_name_dies_with_the_name(folder, name, capsys):
    import run

    with pytest.raises(LookupError, match=f"{folder}/{name}"):
        byname.load(folder, name)
    with pytest.raises(SystemExit):
        run.load_module(folder, name)
    assert f"{folder}/{name}.py" in capsys.readouterr().err
    if folder == "lib/keymix":
        with pytest.raises(LookupError, match=name):
            Stream(1, 4, 100, {"kind": name}, 16)


def test_a_mix_that_leaves_its_domain_is_refused(second_deployment):
    (second_deployment / "lib/keymix/wide.py").write_text(
        "import numpy as np\n"
        "def ranks(block, seed, domain, mix, n):\n"
        "    return np.full(n, domain, np.int64)\n")
    with pytest.raises(ValueError, match="wide"):
        Stream(1, 4, 100, {"kind": "wide"}, 16).ranks(0, 1)


def test_must_stay_zero_is_what_run_py_held():
    """``run.py``'s constants until PR 25, now the configuration's."""
    with open(os.path.join(BENCH, "configs", "gbm500_keyed.json")) as fh:
        cfg = json.load(fh)
    assert cfg["must_stay_zero"] == [
        "fallback_records", "redispatch_records", "oom_shrinks",
        "state_bypass_records", "state_rollbacks", "state_evictions",
        "state_overflow", "device_fault_total*", "dlq_records*",
    ]
    assert (cfg["model_kind"], cfg["warmup_check"]) == ("gbm", "all_resident")


def test_no_deployment_is_known_by_name_in_the_harness():
    for rel in ("run.py", "lib/stream.py"):
        with open(os.path.join(BENCH, rel)) as fh:
            text = fh.read()
        for word in ("zipf", "gbm_ref", "gen_arrays", "ZERO_COUNTERS"):
            assert word not in text, f"{rel} names {word}"


# -- the comparison that decides ``correct`` ---------------------------

@pytest.mark.parametrize("seed", [2**31 + 21, 2**31 + 22, 2**31 + 23, 5])
def test_the_control_fails_the_score_limit(seed):
    """The control of PERF.md §4: the reference put in the program's
    place with every leaf rounded to bfloat16 (the precision below the
    configuration's bf16 hi+lo pair) and summed in float32, at the
    cell's own model size. It has to miss the limit; the same sum of
    hi+lo pairs, which is what the configuration states, has to hold
    it."""
    import ml_dtypes

    model = byname.load("models", "gbm")
    with open(os.path.join(BENCH, "configs", "gbm500_keyed.json")) as fh:
        m = json.load(fh)["model"]
    g = model.generate(seed, m)
    X = Stream(seed, m["n_features"], 150_000_000, ZIPF, 4096).rows(0, 4096)
    ref = model.reference_scores(g, X)

    def miss(leaf32):
        lowered = dataclasses.replace(g, leaf=leaf32.astype(np.float64))
        got = model.reference_scores(lowered, X).astype(np.float32)
        return float((np.abs(got - ref) / (
            model.SCORE_ATOL + model.SCORE_RTOL * np.abs(ref))).max())

    hi = g.leaf.astype(ml_dtypes.bfloat16).astype(np.float32)
    lo = (g.leaf - hi).astype(ml_dtypes.bfloat16).astype(np.float32)
    assert miss(hi) > 3.0            # the control: not correct
    assert miss(hi + lo) < 0.6       # what the configuration states


def tiny_run(monkeypatch, break_sink):
    """One whole run of the first cell at the rehearsal's tiny size on
    the CPU, the harness's look for a chip skipped, with the sink's
    ``on_batch`` wrapped by ``break_sink``."""
    import rehearse
    import run

    inner = run.Sink.on_batch
    monkeypatch.setattr(
        run.Sink, "on_batch",
        lambda self, first, n, scores, t: break_sink(
            inner, self, first, n, scores, t))
    args = argparse.Namespace(workload=rehearse.cells()[0], seed=2**31 + 31,
                              seconds=2.0, trace=0)
    return run.run_cell(args, overrides=rehearse.TINY, on_chip=False)


def test_an_answer_altered_in_the_window_is_not_correct(monkeypatch):
    n_warm = 4196  # rehearse.TINY's warm-up stream: the check before
    # the window passes, the one after it has to catch this

    def alter(inner, sink, first, n, scores, t):
        if first >= n_warm:
            scores = np.array(scores, copy=True)
            scores[::7] += 1e-2
        inner(sink, first, n, scores, t)

    res = tiny_run(monkeypatch, alter)
    assert res["correct"] is False and res["failed"] == 0
    c = res["compared"]
    assert c["warmup_score_miss_over_tol"]["value"] < 1.0
    assert c["window_score_miss_over_tol"]["value"] > 10.0
    assert list(res)[-1] == "compared"


def test_half_a_batch_left_out_is_not_correct(monkeypatch):
    def halve(inner, sink, first, n, scores, t):
        if first >= 4196:
            n = n // 2
        inner(sink, first, n, scores[:n], t)

    res = tiny_run(monkeypatch, halve)
    assert res["correct"] is False and res["failed"] > 0
    assert res["compared"]["offsets_lost"]["value"] > 0
