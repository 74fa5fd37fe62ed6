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


def decode_segs(segs, n_features):
    from flink_jpmml_tpu.runtime.kafka import decode_record_batches_rows

    assert [a for a, _, _ in segs[1:]] == [b for _, b, _ in segs[:-1]]
    return decode_record_batches_rows(
        b"".join(blob for _, _, blob in segs), n_features)


def decode_log(broker, n_features):
    return decode_segs(list(broker._segs[0]), n_features)


def tap_publish(gen):
    """→ the list every published segment is also appended to: the log
    itself is trimmed behind the sink."""
    published, real = [], gen.broker.publish

    def publish(segs):
        published.extend(segs)
        real(segs)

    gen.broker.publish = publish
    return published


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
    published = tap_publish(gen)
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
        offs, rows = decode_segs(published, 32)
        assert np.array_equal(offs, np.arange(stats["produced"]))
        want = Stream(seed, 32, INIT["key_domain"], ZIPF, 16384).rows(
            0, stats["produced"])
        assert np.array_equal(rows.view(np.uint32), want.view(np.uint32))
        # segment boundaries do not move with the threads: 512 from the
        # start of each stretch that was appended
        bounds = {a for a, _, _ in published}
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
    assert list(res)[-3:] == ["window", "broken", "compared"]
    assert "window_score_miss_over_tol" in res["broken"]
    assert list(c)[:len(res["broken"])] == res["broken"]  # the broken first


def test_half_a_batch_left_out_is_not_correct(monkeypatch):
    def halve(inner, sink, first, n, scores, t):
        if first >= 4196:
            n = n // 2
        inner(sink, first, n, scores[:n], t)

    res = tiny_run(monkeypatch, halve)
    assert res["correct"] is False and res["failed"] > 0
    assert res["compared"]["offsets_lost"]["value"] > 0
    assert "offsets_lost" in res["broken"]
    assert list(res["compared"])[:len(res["broken"])] == res["broken"]


# -- what PR 33 added: the table's stamps, the opening rule, the cores,
# -- the trimmed log, ``broken`` -------------------------------------------

def test_the_fill_stamps_every_slot_it_places_and_no_other():
    from flink_jpmml_tpu.runtime.state import KeyedStateTable, StateSpec
    from flink_jpmml_tpu.utils.metrics import MetricsRegistry
    from lib import prefill

    cap, n_keys = 61001, 45000  # the rehearsal's table: one key wraps
    table = KeyedStateTable(
        StateSpec(capacity=cap, key_col=0, probe=64, decay=0.999,
                  stride=1 << 20), metrics=MetricsRegistry())
    said = []
    prefill.back_mirror(table, said.append)
    assert not table._touch.any() and not table._occ.any()
    prefill.apply_fill(table, prefill.plan_fill(n_keys, cap), said.append)
    assert table.resident == n_keys
    with pytest.raises(RuntimeError, match="holds no key"):
        prefill.back_mirror(table, said.append)
    assert np.array_equal(table._touch != 0, table._occ)
    assert int(table._occ.sum()) == n_keys
    # one stamp for the bulk, under every sequence number a later call
    # takes; the key that wrapped went through the table's own routing
    stamps = np.unique(table._touch[table._occ])
    assert stamps[0] == 1 and stamps[-1] <= table._seq
    assert any("resident bytes" in line and "stamped 1" in line
               for line in said)
    # the table finds every key where the fill put it, and stamps on
    first = prefill.crc32_of_ids(prefill.ids_of_ranks(np.arange(0, 2000)))
    slots, reset, _, _ = table.assign_slots(
        np.unique(first), np.zeros(np.unique(first).size, np.int64))
    assert not reset.any() and (slots != table.scratch).all()
    assert (table._touch[slots] == table._seq).all()


def test_resident_bytes_counts_the_pages_that_were_written():
    from lib import prefill

    a = np.zeros(1 << 22, np.int64)  # 32 MiB of untouched zero pages
    before = prefill.resident_bytes(a)
    if before is None:
        pytest.skip("mincore is not to be had here")
    a[:: 512] = 1  # one word a page
    assert before < a.nbytes // 8 and prefill.resident_bytes(a) >= a.nbytes


@pytest.mark.parametrize("n,producer", [(1, 0), (4, 1), (13, 4), (96, 12)])
def test_the_cores_are_split_by_one_rule(n, producer):
    from lib import cores

    allowed = [3 * i + 1 for i in range(n)]  # not 0..n-1: a cpuset's own
    s = cores.split(allowed)
    assert len(s["producer"]) == producer and s["why"]
    if not producer:
        assert s["pipeline"] == [] and "nothing is pinned" in s["why"]
        return
    assert set(s["pipeline"]).isdisjoint(s["producer"])
    assert sorted(s["pipeline"] + s["producer"]) == allowed
    assert len(s["pipeline"]) >= 2 * len(s["producer"])
    assert min(s["producer"]) > max(s["pipeline"])
    assert 1 <= cores.encoders_for(producer) <= 8


class _FakeChild:
    """Answers ``delivered`` as a producer that fills its backlog
    ``fills_after`` seconds after it was made (never: None)."""

    def __init__(self, fills_after):
        self.t0, self.fills_after = time.monotonic(), fills_after

    def ask(self, **msg):
        age = time.monotonic() - self.t0
        return {"produced": int(1000 * age), "filled": (
            self.fills_after is not None and age >= self.fills_after)}


def test_the_window_does_not_open_before_the_backlog_is_full():
    import run

    child = _FakeChild(fills_after=0.6)
    watch = run.LeadWatch(child, lambda: 0)
    try:
        started = {"t0": child.t0, "first_offset": 0}
        full_after = watch.wait_for_backlog(
            started, {"settle_s": 0.1, "backlog_records": 500}, lambda: None)
        opened = time.monotonic() - child.t0
    finally:
        watch.stop()
    assert 0.6 <= full_after <= opened < 2.0
    # and no earlier than the settling, where the backlog fills at once
    child = _FakeChild(fills_after=0.0)
    watch = run.LeadWatch(child, lambda: 0)
    try:
        watch.wait_for_backlog({"t0": child.t0, "first_offset": 0},
                               {"settle_s": 0.5, "backlog_records": 500},
                               lambda: None)
        assert time.monotonic() - child.t0 >= 0.5
    finally:
        watch.stop()


def test_a_producer_that_never_fills_its_backlog_gets_no_window(
        monkeypatch, capsys):
    """The whole tiny run, its producer held from the start under what
    the backlog needs: it ends by itself, with the sentence, and well
    inside a run's time limit."""
    import rehearse
    import run

    monkeypatch.setattr(run, "BACKLOG_WAIT_S", 1.5)
    args = argparse.Namespace(workload=rehearse.cells()[0], seed=2**31 + 33,
                              seconds=2.0, trace=0)
    t0 = time.monotonic()
    with pytest.raises(SystemExit) as stopped:
        run.run_cell(args, overrides=rehearse.TINY_NEVER_FULL, on_chip=False)
    assert stopped.value.code == 1 and time.monotonic() - t0 < 120.0
    err = capsys.readouterr().err
    assert "did not fill its backlog of 131072 records" in err
    assert "records/s; no window was opened" in err


@pytest.mark.parametrize("which", [0, 1])
def test_a_starved_tiny_run_names_the_lead(which):
    """Each cell, held from the moment its backlog was full: the window
    opens, the pipeline drains the log, and the result says which number
    broke (``rehearse.py``'s third run of a cell)."""
    import jax

    import rehearse
    import run

    cell = rehearse.cells()[which]
    args = argparse.Namespace(workload=cell, seed=2**31 + 34, seconds=2.0,
                              trace=0)
    chips = run.load_cell(args, None)[0]["chips"]
    if len(jax.devices()) < chips:
        pytest.skip(f"{cell} needs {chips} devices")
    res = run.run_cell(args, overrides=rehearse.TINY_STARVED, on_chip=False)
    assert res["correct"] is False and res["failed"] == 0
    # (with a cold compile cache a CPU run may also compile in its window)
    named = [n for n in res["broken"] if n != "compilations_in_window"]
    assert named and all(n.startswith("least_lead_records.") for n in named)
    assert list(res["compared"])[:len(res["broken"])] == res["broken"]
    assert res["compared"]["backlog_full_after_s"]["holds"]


def test_compared_puts_the_broken_first_and_leaves_no_fault_unnamed():
    import run

    compared = [("warmup_score_miss_over_tol", 0.2, 1.0),
                ("offsets_lost", 3, 0),
                ("least_lead_records.harness", 10, 5, True),
                ("counter.state_overflow", 0, 0),
                ("least_lead_records.producer", None, 5, False)]
    got = run.settle_compared(compared, ["two faults", "said"])
    assert [e[0] for e in got] == [
        "offsets_lost", "least_lead_records.producer",
        "least_lead_records.harness", "counter.state_overflow",
        "warmup_score_miss_over_tol"]
    assert [e[3] for e in got] == [False, False, True, True, True]
    # a fault whose check gave no number that does not hold gets one
    got = run.settle_compared(compared[:1], ["the state check said so"])
    assert got[0] == ("faults_without_a_number", 1, 0, False)
    assert run.settle_compared(compared[:1], []) == [
        ("warmup_score_miss_over_tol", 0.2, 1.0, True)]


def test_a_trimmed_log_still_serves_every_offset_after_the_warm_up():
    """The producer's own loop with a real consumer behind it: the log
    is trimmed behind the sink, and what the consumer read from the
    warm-up's end on has the digest of the stream, offset by offset."""
    from flink_jpmml_tpu.runtime.kafka import KafkaBlockSource
    from flink_jpmml_tpu.utils.metrics import MetricsRegistry

    seed, n_warm, backlog = 2**31 + 35, 4196, 32_768
    gen = loadgen.Generator(dict(INIT, seed=seed, pool_rows=512))
    source, read, hi = None, hashlib.sha256(), n_warm
    try:
        gen.append(0, n_warm)
        source = KafkaBlockSource(gen.broker.host, gen.broker.port, "bench",
                                  n_cols=32, max_wait_ms=5,
                                  metrics=MetricsRegistry())
        source.seek(n_warm)
        gen.start({"loop": "closed_backlog", "chunk_records": 8192,
                   "backlog_records": backlog,
                   "producer_max_records_per_s": None}, n_warm)
        deadline = time.monotonic() + 120.0
        while hi < n_warm + 12 * backlog and time.monotonic() < deadline:
            item = source.poll()
            if item is None:
                continue
            first, rows = item
            assert first == hi
            read.update(np.ascontiguousarray(rows).tobytes())
            hi = first + rows.shape[0]
            gen.note_delivered(hi)
        stats = gen.stop()
        assert hi >= n_warm + 12 * backlog
        # the log was trimmed: its first segment starts past the
        # warm-up, within two backlogs and a chunk of the sink
        kept_from = gen.broker._segs[0][0][0]
        assert n_warm < kept_from and hi - kept_from <= 2 * backlog + 8192
        assert stats["backlog_full_after_s"] is not None
    finally:
        if source is not None:
            source.close()
        gen.close()
    want = Stream(seed, 32, INIT["key_domain"], ZIPF, 512).rows(n_warm, hi)
    assert read.hexdigest() == hashlib.sha256(want.tobytes()).hexdigest()


def test_a_producer_held_from_the_fill_keeps_to_its_rate_after_it():
    gen = loadgen.Generator(dict(INIT, seed=4))
    try:
        gen.start({"loop": "closed_backlog", "chunk_records": 8192,
                   "backlog_records": 65_536, "producer_max_from": "filled",
                   "producer_max_records_per_s": 20_000}, 0)
        deadline = time.monotonic() + 60.0
        while not gen.filled and time.monotonic() < deadline:
            time.sleep(0.005)
        assert gen.filled
        full = gen.broker.produced
        gen.note_delivered(full)  # the sink takes the whole backlog
        time.sleep(1.0)
        stats = gen.stop()
        assert 8192 <= stats["produced"] - full <= 20_000 * 1.5 + 8192
    finally:
        gen.close()


def test_the_score_thread_reader_books_the_mesh_stages():
    stages = {"drain": 0.10, "encode": 0.10, "route": 0.10, "h2d": 0.10,
              "shard": 0.25, "unshard": 0.05, "queue_wait": 0.10, "sink": 0.05,
              "fetch": 5.0, "decode": 5.0, "prefetch_wait": 5.0}
    hist = {f'stage_seconds{{stage="{k}"}}': {"sum": v, "n": 3}
            for k, v in stages.items()}
    ctx = {"snap0": {"histograms": {}, "counters": {}, "ts": 10.0},
           "snap1": {"histograms": hist, "counters": {}, "ts": 11.0}}
    read = byname.load("layer_metrics", "score_thread_booked_frac.sat").read
    assert read(ctx) == pytest.approx(85.0)
    del hist['stage_seconds{stage="shard"}']
    del hist['stage_seconds{stage="unshard"}']
    assert read(ctx) == pytest.approx(55.0)  # one chip books neither
