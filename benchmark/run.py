#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in ``BENCHMARK.json``, loads its configuration
(``configs/<config>.json``), its traffic mix (``traffic/<mix>.json``,
with ``traffic/<mix>.<config>.json`` laid over it where a cell has
parameters of its own), what the configuration names (its path builder
``paths/<path>.py``, its model kind ``models/<model_kind>.py``, its
warm-up check ``warmup_checks/<warmup_check>.py``, the counters that
``must_stay_zero``), the mix's key mix (``lib/keymix/<kind>.py``) and
one reader per metric the cell reports (``end_to_end/<metric>.py``,
``layer_metrics/<metric>.py``). Nothing in this file knows a cell, a
configuration, a model, a mix or a metric by name.

One process holds the chip; the broker and the producer live in a
jax-free child (``lib/loadgen.py``). Everything before the window is
set-up: model from the seed, parse, compile (or cache load), the state
table filled to the deployment's resident keys (``lib/prefill.py``),
every dispatch shape the window can use, a warm-up stream through the
real pipeline, and the check of that stream against the plain
reference. Once the window has closed, a sample of the scores it
delivered, drawn from the seed, is held to the reference too.

The producer's child and this process pin themselves to cores of their
own (``lib/cores.py``), and the window opens at the later of
``settle_s`` after the producer's start and the producer's first word
that its backlog has been full; a producer that cannot fill it in
``BACKLOG_WAIT_S`` gets no window, and the run ends with a sentence.

Every number that decides ``correct`` is printed beside its limit, as
the last lines of stderr and under ``compared``, the last key of the
result: the entries that do not hold first (their names alone under
``broken``, the key before), then the leads and the counters, then the
rest; on stderr the same, the ``BROKEN`` lines last. Nothing makes a
run not correct without an entry there. ``window``, the key before
those, is what the window was made of: the program's stage sums, the
delivery intervals, the producer's rates, the lead at the opening and
both processes' resident sets. The last line of stdout is the result;
without a TPU, or with fewer chips than the cell asks for, the run
exits non-zero and prints none.
"""

import time

_T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import concurrent.futures  # noqa: E402
import faulthandler  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)   # lib, reference, paths
sys.path.insert(1, ROOT)   # the program under test

STALL_S = 8.0  # a dispatch takes under a second
# a producer that cannot fill its backlog beside the pipeline in this
# long is a finding, not a window
BACKLOG_WAIT_S = 20.0
# ``compared`` after the entries that do not hold: what decides a hard
# run, then the rest in the order the checks ran
COMPARED_HEAD = ("least_lead_records.", "backlog_full_after_s",
                 "longest_delivery_gap_s", "counter.")
# the window's scores held to the reference: runs of consecutive
# offsets at places drawn from the seed (a run costs one block of keys)
WINDOW_SAMPLE_RUNS, WINDOW_SAMPLE_RUN = 64, 64


def log(msg: str) -> None:
    print(f"# {msg}", flush=True)


def die(msg: str) -> None:
    print(f"benchmark: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts), encoding="utf-8") as fh:
        return json.load(fh)


def load_module(kind: str, name: str):
    from lib import byname

    try:
        return byname.load(kind, name)
    except LookupError as e:
        die(str(e))


def named(cfg: dict, key: str):
    """What the configuration names under ``key``."""
    if key not in cfg:
        die(f"configuration {cfg.get('name')!r} names no {key!r}")
    return cfg[key]


class Child:
    """The load generator process and its line protocol."""

    def __init__(self):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "lib", "loadgen.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env,
            text=True, bufsize=1, cwd=ROOT,
        )
        self._mu = threading.Lock()

    def send(self, **msg) -> None:
        self.proc.stdin.write(json.dumps(msg) + "\n")
        self.proc.stdin.flush()

    def read(self) -> dict:
        line = self.proc.stdout.readline()
        if not line:
            raise RuntimeError(
                f"load generator died (exit {self.proc.poll()})"
            )
        return json.loads(line)

    def ask(self, **msg) -> dict:
        """One command and its reply; two threads ask (the harness and
        the backlog's feedback), one at a time."""
        with self._mu:
            self.send(**msg)
            return self.read()

    def close(self) -> None:
        if self.proc.poll() is None:
            try:
                self.send(cmd="exit")
                self.proc.stdin.close()
                self.proc.wait(timeout=10.0)
            except (OSError, subprocess.TimeoutExpired):
                self.proc.kill()
        self.proc.wait()


class LeadWatch:
    """The producer follows the sink through this thread, and the
    harness takes the log's real lead from it: what the broker holds
    (the child's reply) less what the sink has by then, every 10 ms. A
    thread of its own, because starting and stopping a trace blocks
    the main one for seconds."""

    def __init__(self, child: Child, delivered_hi):
        # (time, records the log is ahead of the sink, the log's head)
        self.leads = []
        self.filled_at = None  # the first reply that said ``filled``
        self._child, self._delivered_hi = child, delivered_hi
        self._done = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self) -> None:
        while not self._done.is_set():
            hi = self._delivered_hi()
            try:
                reply = self._child.ask(cmd="delivered", n=hi)
            except (OSError, ValueError, RuntimeError):
                return  # the child is gone: the run is on its way out
            now = time.monotonic()
            self.leads.append((now, reply["produced"] - hi, reply["produced"]))
            if self.filled_at is None and reply.get("filled"):
                self.filled_at = now
            time.sleep(0.01)

    def stop(self) -> None:
        self._done.set()
        self._thread.join(timeout=10.0)

    def least(self, w0: float, w1: float) -> dict:
        """The least lead sampled inside [w0, w1], and when."""
        in_w = [(a, t - w0) for t, a, _ in self.leads if w0 <= t <= w1]
        return dict(zip(("least", "at_s"), min(in_w, default=(None, None))),
                    samples=len(in_w))

    def produced_at(self, *times: float):
        """The log's head at those instants, between the samples."""
        t, _, head = zip(*self.leads)
        return np.interp(times, t, head).tolist()

    def wait_for_backlog(self, started: dict, traffic: dict, alive) -> float:
        """Sleeps to the later of ``settle_s`` after the producer's
        start and the first reply that says its backlog has been full →
        the seconds from its start to that reply. The window opens
        there. Past ``BACKLOG_WAIT_S`` the run ends with a sentence."""
        t0 = float(started["t0"])
        while self.filled_at is None:
            alive()
            waited = time.monotonic() - t0
            if waited > BACKLOG_WAIT_S:
                _, lead, head = self.leads[-1] if self.leads else (0, 0, 0)
                made = max(0, head - int(started["first_offset"]))
                die(f"the producer did not fill its backlog of "
                    f"{traffic['backlog_records']} records beside the "
                    f"pipeline: after {waited:.1f} s the log's lead over the "
                    f"sink is {lead} records and the producer has made "
                    f"{made / waited:.0f} records/s; no window was opened")
            time.sleep(0.005)
        time.sleep(max(
            0.0, t0 + float(traffic["settle_s"]) - time.monotonic()))
        return self.filled_at - t0


class Sink:
    """What reached the sink: per delivery its range of offsets, the
    time, and whether the scores were finite. Appends are atomic under
    the interpreter lock; one thread delivers."""

    def __init__(self):
        self.deliveries = []  # (first_offset, n, t_done)
        self.scores = None    # offset → score while the warm-up is kept
        self.kept = None      # (first_offset, scores) of every delivery
        self.keep_until = float("inf")  # ... that is done by then
        self.nonfinite = 0
        self.delivered_hi = 0
        self.count = 0

    def keep_scores(self, n: int) -> None:
        self.scores = np.full(n, np.nan, np.float64)

    def on_batch(self, first, n, scores, t_done) -> None:
        if not np.isfinite(scores).all():
            self.nonfinite += int((~np.isfinite(scores)).sum())
        if self.scores is not None and first < self.scores.shape[0]:
            keep = min(n, self.scores.shape[0] - first)
            self.scores[first:first + keep] = scores[:keep]
        kept = self.kept  # the harness takes the list away: read it once
        if kept is not None and t_done <= self.keep_until:
            kept.append((first, scores))
        self.deliveries.append((first, n, t_done))
        self.delivered_hi = max(self.delivered_hi, first + n)
        self.count += n

    def offset_counts(self, hi: int):
        counts = np.zeros(hi, np.int32)
        for first, n, _ in list(self.deliveries):
            counts[first:first + n] += 1
        return counts


def require_device(chips: int, on_chip: bool):
    import jax

    from lib import peaks

    backend = jax.default_backend()
    devices = jax.devices()
    if not on_chip:
        return jax, devices, None
    if backend != "tpu":
        die(f"needs a TPU backend; JAX resolved {backend!r} with "
            f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}")
    if len(devices) < chips:
        die(f"cell needs {chips} chip(s), JAX found {len(devices)}")
    try:
        pk = peaks.peaks_for(devices[0].device_kind)
    except KeyError as e:
        die(str(e.args[0]))
    return jax, devices, pk


def score_miss(model, handle, X, got):
    """→ (largest miss of a delivered score, as a share of what the
    model kind allows: ``|got - ref| / (atol + rtol |ref|)``; largest
    absolute difference)."""
    ref = model.reference_scores(handle, X)
    diff = np.abs(np.asarray(got, np.float64) - ref)
    allowed = model.SCORE_ATOL + model.SCORE_RTOL * np.abs(ref)
    return float((diff / allowed).max()), float(diff.max())


def check_warmup_scores(stream, model, handle, sink, n_warm: int):
    """A sample of the delivered warm-up scores (offsets below
    ``n_warm``) against the model kind's plain reference → (faults,
    compared)."""
    got = sink.scores[:n_warm]
    missing = int(np.isnan(got).sum())
    if missing:
        return ([f"{missing} warm-up records never delivered"],
                [("warmup_records_missing", missing, 0)])
    sample = np.arange(0, n_warm, max(1, n_warm // 4096))
    miss, diff = score_miss(
        model, handle, stream.rows(0, n_warm)[sample], got[sample])
    log(f"reference: {sample.size} sampled warm-up scores, max |diff| "
        f"{diff:.3e}, largest miss {miss:.3f} of rtol {model.SCORE_RTOL} "
        f"atol {model.SCORE_ATOL}")
    faults = [] if miss <= 1.0 else [
        "warm-up scores differ from the reference"]
    return faults, [("warmup_score_miss_over_tol", miss, 1.0)]


def check_window_scores(seed, stream, model, handle, kept, span):
    """Scores the window delivered against the plain reference, once it
    has closed: ``WINDOW_SAMPLE_RUNS`` runs of ``WINDOW_SAMPLE_RUN``
    consecutive offsets, placed by the seed among the offsets delivered
    in ``span`` (first, one past last) → (faults, compared)."""
    lo, hi = span
    if hi - lo < WINDOW_SAMPLE_RUN:
        return (["no delivery inside the window to hold to the reference"],
                [("window_records_to_sample", hi - lo, WINDOW_SAMPLE_RUN,
                  False)])
    rng = np.random.default_rng([seed, 2])
    starts = np.unique(rng.integers(
        lo, hi - WINDOW_SAMPLE_RUN + 1, size=WINDOW_SAMPLE_RUNS))
    firsts = np.array([f for f, _ in kept], np.int64)
    got = np.full((starts.size, WINDOW_SAMPLE_RUN), np.nan)
    for i, a in enumerate(starts.tolist()):
        for off in range(a, a + WINDOW_SAMPLE_RUN):
            # deliveries are in offset order: the last that starts at
            # or before the offset holds it, or nothing does
            j = int(np.searchsorted(firsts, off, side="right")) - 1
            first, scores = kept[j]
            if j >= 0 and off - first < scores.shape[0]:
                got[i, off - a] = scores[off - first]
    missing = int(np.isnan(got).sum())
    if missing:
        return ([f"{missing} sampled offsets of the window have no score"],
                [("window_sample_missing", missing, 0)])
    X = np.concatenate([
        stream.rows(a, a + WINDOW_SAMPLE_RUN) for a in starts.tolist()])
    miss, diff = score_miss(model, handle, X, got.reshape(-1))
    log(f"reference: {got.size} scores of the window, max |diff| "
        f"{diff:.3e}, largest miss {miss:.3f} of the tolerance")
    faults = [] if miss <= 1.0 else [
        "scores delivered in the window differ from the reference"]
    return faults, [("window_score_miss_over_tol", miss, 1.0)]


def describe_window(w0, w1, deliveries, watch, started, full_after, snaps,
                    rss, split) -> dict:
    """What the window was made of, for the reader of a slow run: the
    program's stage sums between the two snapshots (seconds, count),
    the intervals between deliveries (the window's edges close the
    first and the last), the producer's rate while it filled its
    backlog and over the window, the log's lead at the opening, both
    processes' resident sets at the two edges, the cores."""
    from lib import readers

    t = np.array([w0] + [d[2] for d in deliveries if w0 <= d[2] <= w1] + [w1])
    gaps = np.diff(t)
    longest = int(gaps.argmax())
    between = gaps[1:-1] if gaps.size > 2 else gaps
    t0, first = float(started["t0"]), int(started["first_offset"])
    at_full, at_w0, at_w1 = watch.produced_at(t0 + full_after, w0, w1)
    lead0 = next((a for ts, a, _ in watch.leads if ts >= w0), None)
    return {
        "stage_s": {k: [round(v[0], 4), v[1]] for k, v in
                    readers.stage_sums({"snap0": snaps[0], "snap1": snaps[1]}
                                       ).items() if v[1]},
        "delivery_ms": {
            "n": int(gaps.size - 1),
            "median": round(1e3 * float(np.median(between)), 3),
            "p99": round(1e3 * float(np.quantile(between, 0.99)), 3),
            "longest": round(1e3 * float(gaps[longest]), 3),
            "longest_began_at_s": round(float(t[longest] - w0), 3),
        },
        "producer_records_per_s": {
            "filling": round((at_full - first) / max(full_after, 1e-9)),
            "window": round((at_w1 - at_w0) / (w1 - w0)),
        },
        "backlog_full_after_s": round(full_after, 3),
        "lead_at_open": lead0,
        "rss_bytes": rss,
        "cores": {k: len(split[k]) for k in ("pipeline", "producer")},
    }


def settle_compared(compared, faults):
    """→ the entries of ``compared`` as ``(name, value, limit, holds)``
    in the order the result gives them: those that do not hold, then
    the leads and the counters, then the rest. A limit is the most a
    number may be, unless its entry says itself whether it holds (a
    lead is a least). A fault that left no entry that does not hold
    gets one, so that a run is never not correct without a number."""
    entries = [(n, v, lim, bool(h[0]) if h else v <= lim)
               for n, v, lim, *h in compared]
    if faults and all(e[3] for e in entries):
        entries.append(("faults_without_a_number", len(faults), 0, False))

    def rank(e):
        return 0 if not e[3] else 1 if e[0].startswith(COMPARED_HEAD) else 2

    return sorted(entries, key=rank)  # stable: the checks' order within a rank


def load_cell(args, overrides):
    """→ (cell, cfg, traffic, path module, wanted metrics, readers)."""
    manifest = load_json(ROOT, "BENCHMARK.json")
    cell = next(
        (w for w in manifest["workloads"] if w["name"] == args.workload), None
    )
    if cell is None:
        die(f"no workload {args.workload!r} in BENCHMARK.json")
    cfg = load_json(HERE, "configs", f"{cell['config']}.json")
    traffic = load_json(HERE, "traffic", f"{cell['traffic']}.json")
    overlay = os.path.join(
        HERE, "traffic", f"{cell['traffic']}.{cell['config']}.json"
    )
    if os.path.isfile(overlay):
        traffic.update(load_json(overlay))
    for section, values in (overrides or {}).items():
        target = {"cfg": cfg, "traffic": traffic}[section]
        for k, v in values.items():
            if isinstance(v, dict):
                target[k].update(v)
            else:
                target[k] = v
    group, folder = (
        ("per_layer", "layer_metrics") if args.trace
        else ("end_to_end", "end_to_end")
    )
    wanted = [
        m for m in manifest[group]
        if "workloads" not in m or cell["name"] in m["workloads"]
    ]
    readers = {m["name"]: load_module(folder, m["name"]).read for m in wanted}
    return cell, cfg, traffic, load_module("paths", cfg["path"]), wanted, readers


def wait_for_warmup(path, sink, n_warm: int) -> None:
    deadline = time.monotonic() + 300.0
    while sink.count < n_warm:
        path.check_alive()
        if time.monotonic() > deadline:
            die(f"warm-up stream: {sink.count}/{n_warm} delivered")
        time.sleep(0.01)
    time.sleep(0.05)  # the last dispatch's commit


class TraceStretch:
    """A profiler trace of ``seconds`` starting ``after`` seconds into
    the window, under the host annotation ``bench.window``; its idle
    gaps are named for the program's own host spans."""

    def __init__(self, jax, w0: float, after: float, seconds: float,
                 span_prefix: str):
        self._jax = jax
        self._spans = span_prefix  # the host spans idle gaps are named for
        self._start, self._stop = w0 + after, w0 + after + seconds
        self._dir = tempfile.TemporaryDirectory(prefix="bench-trace-")
        self._span = None
        self._done = False

    def tick(self, now: float) -> None:
        if self._span is None and not self._done and now >= self._start:
            opts = self._jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # the host's Python is not slowed
            self._jax.profiler.start_trace(
                self._dir.name, profiler_options=opts
            )
            self._span = self._jax.profiler.TraceAnnotation("bench.window")
            self._span.__enter__()
        elif self._span is not None and now >= self._stop:
            self.finish()

    def finish(self) -> None:
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._jax.profiler.stop_trace()
            self._span, self._done = None, True

    def reduce(self) -> dict:
        from lib import xtrace

        self.finish()
        try:
            xp = xtrace.find_xplane(self._dir.name)
            if xp is None:
                return {"devices": 0}
            return xtrace.reduce_trace(
                xp, window_name="bench.window", span_prefix=self._spans)
        finally:
            self._dir.cleanup()


def run_cell(args, overrides=None, on_chip: bool = True) -> dict:
    """→ the result object. ``overrides`` lays values over the cell's
    configuration and traffic files: ``rehearse.py`` sets a tiny size or
    a slow producer with it. ``on_chip=False`` (``rehearse.py`` only)
    lets the run start without a TPU; it then reports no metric."""
    cell, cfg, traffic, path_mod, wanted, readers = load_cell(args, overrides)
    if importlib.util.find_spec("flink_jpmml_tpu") is None:
        die("the program under test (flink_jpmml_tpu) is not in this checkout")
    if traffic["loop"] != "closed_backlog":
        die(f"lib/loadgen.py has no producer for loop {traffic['loop']!r}")
    from lib.stream import Stream

    model = load_module("models", named(cfg, "model_kind"))
    warmup_check = load_module("warmup_checks", named(cfg, "warmup_check"))
    zero_names = [str(n) for n in named(cfg, "must_stay_zero")]
    m = cfg["model"]
    try:
        stream = Stream(
            args.seed, m["n_features"], cfg["key_domain"], traffic["key_mix"],
            traffic["pool_rows"],
        )
    except LookupError as e:  # the mix names a key mix there is no file for
        die(str(e))
    from lib import cores

    # before JAX, the planner and the pipeline start a thread: each
    # inherits this thread's set
    may_run_on = cores.allowed()
    split = cores.split(may_run_on)
    cores.pin(split["pipeline"])
    log(f"cores: {split['why']}: pipeline {split['pipeline']}, "
        f"producer {split['producer']}")
    child = Child()
    path = watch = None
    planner = concurrent.futures.ThreadPoolExecutor(max_workers=1)
    try:
        child.send(
            cmd="init", seed=args.seed, topic="bench",
            n_features=cfg["model"]["n_features"],
            key_domain=cfg["key_domain"], key_mix=traffic["key_mix"],
            pool_rows=traffic["pool_rows"],
            cores=split["producer"],  # it pins itself before its threads
        )
        from lib import prefill

        # where the resident keys sit is numpy alone: it runs beside
        # JAX's start-up, the model and the table's allocation
        plan = planner.submit(
            prefill.plan_fill, int(cfg["resident_keys_at_start"]),
            int(cfg["table_slots"]),
        )
        jax, devices, peaks_row = require_device(cell["chips"], on_chip)

        from lib import readers as readers_lib
        from lib import xtrace

        compiles = []
        jax.monitoring.register_event_duration_secs_listener(
            lambda event, secs, **kw: compiles.append(event)
            if "compile" in event or "cache_retrieval" in event else None
        )
        from flink_jpmml_tpu.compile import compile_pmml
        from flink_jpmml_tpu.pmml import parse_pmml_file

        # -- set-up ----------------------------------------------------
        handle = model.generate(args.seed, m)
        with tempfile.TemporaryDirectory(prefix="bench-model-") as d:
            doc = parse_pmml_file(model.write_pmml(handle, d))
        compiled = compile_pmml(doc, batch_size=int(cfg["compile_batch"]))
        log(f"model parsed and lowered at {time.monotonic() - _T_PROCESS:.1f}s")
        addr = dict(child.read(), topic="bench")
        log(f"load generator: pid {addr['pid']}, pinned {addr['pinned']}, "
            f"{addr['encoders']} encoders")
        sink = Sink()
        n_warm = int(cfg["warmup_records"])
        sink.keep_scores(n_warm)
        path = path_mod.Path(cfg, compiled, addr, sink.on_batch)
        facts = path.facts()
        log(f"backends: {json.dumps(facts)}")
        if on_chip:
            for k, want in cfg["expected_backends"].items():
                if facts.get(k) != want:
                    die(f"{k} is {facts.get(k)!r}, the configuration "
                        f"states {want!r}")
        log(f"table built at {time.monotonic() - _T_PROCESS:.1f}s")
        prefill.back_mirror(path.table, log)  # while the plan is made
        path.fill_table(args.seed, plan.result(), log)
        log(f"table filled at {time.monotonic() - _T_PROCESS:.1f}s")
        path.warm_shapes()
        log(f"shapes warm at {time.monotonic() - _T_PROCESS:.1f}s")

        def state_records():
            return path.metrics.struct_snapshot()["counters"]["state_records"]

        # what the fill and the check route through the table is not
        # the stream's
        beside = state_records()
        path.start()
        child.ask(cmd="produce", n=n_warm)
        wait_for_warmup(path, sink, n_warm)
        beside -= state_records()
        faults, compared = check_warmup_scores(
            stream, model, handle, sink, n_warm)
        if not faults:
            state_faults, state_compared = warmup_check.check({
                "seed": args.seed, "stream": stream, "n_warm": n_warm,
                "scores": sink.scores[:n_warm], "path": path, "cfg": cfg,
                "log": log,
            })
            faults += state_faults
            compared += state_compared
        beside += state_records()
        sink.scores = None
        sink.kept = []
        log(f"warm-up checked at {time.monotonic() - _T_PROCESS:.1f}s")

        # -- the window ------------------------------------------------
        started = child.ask(cmd="start", traffic=traffic, delivered=n_warm)
        watch = LeadWatch(child, lambda: sink.delivered_hi)
        full_after = watch.wait_for_backlog(started, traffic, path.check_alive)
        w0 = time.monotonic()
        w1 = w0 + float(args.seconds)
        sink.keep_until = w1 + 2 * STALL_S
        setup_s = w0 - _T_PROCESS
        snap0 = path.metrics.struct_snapshot()
        n_compiles0 = len(compiles)
        rss = {"parent": [cores.rss_bytes()],
               "child": [cores.rss_bytes(addr["pid"])]}
        # The window's second snapshot, at its end and on a thread of
        # its own: stopping a trace holds the main loop far past w1
        # (tens of seconds on a long stretch), and a snapshot taken
        # when it gets out would book that time's stages to the window.
        at_end = {}

        def snap_at_end():
            time.sleep(max(0.0, w1 - time.monotonic()))
            at_end["snap1"] = path.metrics.struct_snapshot()
            at_end["compiles"] = len(compiles)
            rss["parent"].append(cores.rss_bytes())
            rss["child"].append(cores.rss_bytes(addr["pid"]))

        closer = threading.Thread(target=snap_at_end, daemon=True)
        closer.start()
        # The traced stretch lies at the window's end: stopping a trace
        # turns millions of events into a file for tens of seconds, on
        # the host's cores, and inside the window that work would be
        # booked to the pipeline's host stages. It ends 1.25 s before
        # the window does: a span that began before the trace is not in
        # it, and at 0.25 s the cell's stretch began inside a renorm's
        # hold in every run, its idle gaps under a span nobody saw.
        stretch_s = min(float(traffic["trace_seconds"]), 0.6 * args.seconds)
        stretch = TraceStretch(
            jax, w0, max(0.0, args.seconds - stretch_s - 1.25), stretch_s,
            readers_lib.SPAN_PREFIX,
        ) if args.trace else None
        seen, since, stalled = sink.count, w0, False
        while (now := time.monotonic()) < w1:
            path.check_alive()
            if stretch is not None:
                stretch.tick(now)
            if sink.count != seen:
                seen, since = sink.count, now
            elif now - since > STALL_S and not stalled:
                # nothing reached the sink for far longer than a
                # dispatch takes: say where every thread stands
                stalled = True
                faulthandler.dump_traceback(file=sys.stderr)
            time.sleep(0.005)
        closer.join(timeout=30.0)
        if closer.is_alive():
            die("the snapshot at the window's end was never taken")
        snap1 = at_end["snap1"]
        n_compiles = at_end["compiles"] - n_compiles0
        if stretch is not None:
            stretch.finish()
        mem_peak = max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in devices
        )
        # the first delivery beyond the window's end: records_per_s
        # shares the dispatch that straddles an edge by time
        while sink.deliveries[-1][2] <= w1 and (
                time.monotonic() < w1 + STALL_S):
            path.check_alive()
            time.sleep(0.005)
        deliveries = list(sink.deliveries)
        kept, sink.kept = sink.kept, None
        hi = sink.delivered_hi
        watch.stop()
        gen = child.ask(cmd="stop")
        path.stop()
        log(f"generator: {json.dumps(gen)}")
        lead = watch.least(w0, w1)
        log(f"log's lead over the sink, from the harness: {json.dumps(lead)}")

        # -- what the window held ---------------------------------------
        counts = sink.offset_counts(hi)
        lost, dup = int((counts == 0).sum()), int((counts > 1).sum())
        in_window = [(t, n) for _, n, t in deliveries if w0 <= t <= w1]
        attempted = int(sum(n for _, n in in_window))
        firsts = [f for f, _, t in deliveries if w0 <= t <= w1]
        window_faults, window_compared = check_window_scores(
            args.seed, stream, model, handle, kept,
            (min(firsts), min(firsts) + attempted) if firsts else (0, 0),
        )
        del kept
        faults += window_faults
        described = describe_window(
            w0, w1, deliveries, watch, started, full_after, (snap0, snap1),
            rss, split)
        log(f"window: {json.dumps(described)}")
        gap_s = described["delivery_ms"]["longest"] / 1e3
        if gap_s > STALL_S:
            faults.append(f"no delivery for {gap_s:.1f} s")
        compared += window_compared + [
            ("offsets_lost", lost, 0), ("offsets_duplicated", dup, 0),
            ("scores_nonfinite", sink.nonfinite, 0),
            ("compilations_in_window", n_compiles, 0),
            ("longest_delivery_gap_s", gap_s, STALL_S),
            ("backlog_full_after_s", full_after, BACKLOG_WAIT_S),
        ]
        allowed = int(traffic["least_backlog_allowed"])
        for who, least in (("harness", lead["least"]),
                           ("producer", gen.get("least_backlog_records"))):
            compared.append((f"least_lead_records.{who}", least, allowed,
                             least is not None and least >= allowed))
            if least is None or least < allowed:
                faults.append(
                    "the log's lead over the sink " + (
                        "was never taken" if least is None
                        else f"fell to {least} records"
                    ) + f" ({who}'s account; {allowed} allowed): the "
                    "window measured the producer"
                )
        final = path.metrics.struct_snapshot()["counters"]
        exact = {n for n in zero_names if not n.endswith("*")}
        prefixes = tuple(n[:-1] for n in zero_names if n.endswith("*"))
        for name in sorted(exact | {
                n for n in final if prefixes and n.startswith(prefixes)}):
            v = final.get(name, 0)
            compared.append((f"counter.{name}", v, 0))
            if v:
                faults.append(f"{name} = {v}")
        # every delivered record was folded once; what was dispatched
        # and not delivered when the pipeline stopped is folded besides
        folded = final.get("state_records", 0) - beside
        slack = (int(cfg["pipeline"]["in_flight"]) + 1) * int(
            cfg["pipeline"].get("max_dispatch_chunks", 1)
        ) * int(cfg["compile_batch"])
        compared.append(("state_records_beyond_delivered",
                         folded - sink.count, slack))
        if not (sink.count <= folded <= sink.count + slack):
            faults.append(
                f"state_records {folded} against {sink.count} delivered"
            )
        if n_compiles:
            faults.append(f"{n_compiles} compilations inside the window")
        failed = lost + dup + sink.nonfinite
        log(f"window: {attempted} records, {len(in_window)} deliveries, "
            f"lost {lost}, duplicated {dup}, non-finite {sink.nonfinite}, "
            f"compilations inside {n_compiles}")

        # -- the result -------------------------------------------------
        device = {
            "platform": str(devices[0].platform),
            "kind": str(devices[0].device_kind),
            "count": len(devices),
            "memory_peak_bytes": int(mem_peak),
        }
        result = {"attempted": attempted, "failed": failed}
        trace_red = None
        if stretch is not None:
            trace_red = stretch.reduce()
            if trace_red["devices"]:
                device["busy_s"] = float(trace_red["busy_s"])
                device["window_s"] = float(trace_red["window_s"])
                result["breakdown"] = {
                    "device_ops": [
                        [k, float(v)] for k, v in trace_red["device_ops"][:10]
                    ],
                    "idle_gaps": xtrace.attribute_gaps(trace_red),
                }
            if on_chip:
                n_ops = len(trace_red.get("device_ops", ()))
                compared.append(("device_ops_in_trace", n_ops, 1, n_ops >= 1))
                if not n_ops:
                    faults.append(
                        "no operation ran on the device in the trace")
        for f in faults:
            log(f"FAULT: {f}")
        ctx = {
            "window": (w0, w1), "window_s": float(args.seconds),
            "deliveries": [(t, n) for _, n, t in deliveries],
            "batches": in_window, "snap0": snap0, "snap1": snap1,
            "gen": gen, "trace": trace_red, "setup_s": setup_s,
            "cfg": cfg, "traffic": traffic, "peaks": peaks_row,
        }
        metrics = {}
        for spec in wanted:
            v = readers[spec["name"]](ctx)
            if v is not None:
                metrics[spec["name"]] = {"value": float(v), "unit": spec["unit"]}
        if not on_chip:
            # a CPU run's numbers are never device metrics
            log(f"rehearsal metrics (not reported): {json.dumps(metrics)}")
            metrics = {}
        result["metrics"] = metrics
        result["device"] = device
        # each number that decided ``correct``, beside its limit: the
        # last key of the line, what does not hold first; and the last
        # lines of stderr, what does not hold last
        entries = settle_compared(compared, faults)
        print(f"window: {json.dumps(described)}", file=sys.stderr, flush=True)
        result["correct"] = all(e[3] for e in entries) and failed == 0
        result["window"] = described
        result["broken"] = [e[0] for e in entries if not e[3]]
        result["compared"] = {
            name: {"value": value, "limit": limit, "holds": ok}
            for name, value, limit, ok in entries}
        for name, value, limit, ok in sorted(entries, key=lambda e: not e[3]):
            print(f"compared: {name} {value} limit {limit} "
                  f"{'holds' if ok else 'BROKEN'}", file=sys.stderr, flush=True)
        return result
    finally:
        planner.shutdown(wait=True, cancel_futures=True)
        if path is not None:
            try:
                path.stop()
            except Exception as e:  # the first error is the one to report
                log(f"stop: {type(e).__name__}: {e}")
        if watch is not None:
            watch.stop()
        child.close()
        cores.pin(may_run_on)  # a caller that goes on (a test) has its own back


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = run_cell(args)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
