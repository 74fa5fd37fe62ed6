#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in ``BENCHMARK.json``, loads its configuration
(``configs/<config>.json``), its traffic mix (``traffic/<mix>.json``,
with ``traffic/<mix>.<config>.json`` laid over it where a cell has
parameters of its own), its path builder (``paths/<path>.py``, named by
the configuration) and one reader per metric the cell reports
(``end_to_end/<metric>.py``, ``layer_metrics/<metric>.py``). Nothing in
this file knows a cell, a configuration, a mix or a metric by name.

One process holds the chip; the broker and the producer live in a
jax-free child (``lib/loadgen.py``). Everything before the window is
set-up: model from the seed, parse, compile (or cache load), the state
table filled to the deployment's resident keys (``lib/prefill.py``),
every dispatch shape the window can use, a warm-up stream through the
real pipeline, and the check of that stream against the plain
reference. The last line of stdout is the
result; without a TPU, or with fewer chips than the cell asks for, the
run exits non-zero and prints none.
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

# tolerance of a delivered score against the float64 tree walk: the rank
# wire carries each leaf as a bf16 hi+lo pair (2**-17 relative,
# compile/qtrees.py _split_bf16) and adds 500 of them in float32, which
# leaves ~2e-5 absolute on sums of magnitude 2-9 (measured 2.2e-5). The
# program's own tests hold the kernel to rtol 1e-4 / atol 1e-5 against
# its XLA twin, which shares that quantisation
# (tests/test_qtrees_pallas.py:41); against an exact walk the absolute
# floor is 5e-5. A bf16-only sum would miss by ~4e-3.
SCORE_RTOL, SCORE_ATOL = 1e-4, 5e-5
STALL_S = 8.0  # a dispatch takes under a second


def log(msg: str) -> None:
    print(f"# {msg}", flush=True)


def die(msg: str) -> None:
    print(f"benchmark: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts), encoding="utf-8") as fh:
        return json.load(fh)


def load_module(kind: str, name: str):
    path = os.path.join(HERE, kind, f"{name}.py")
    if not os.path.isfile(path):
        die(f"no {kind}/{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


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


class Sink:
    """What reached the sink: per delivery its range of offsets, the
    time, and whether the scores were finite. Appends are atomic under
    the interpreter lock; one thread delivers."""

    def __init__(self):
        self.deliveries = []  # (first_offset, n, t_done)
        self.scores = None    # offset → score while the warm-up is kept
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


def check_warmup(stream, model_arrays, sink, path, n_warm: int,
                 first_rows):
    """The delivered warm-up stream (offsets below ``n_warm``) against
    the plain reference → faults. Scores: a sample of delivered scores
    against the float64 tree walk. State: after the stream, each key's
    table row (count, score sum) against the row its slot started with
    (``first_rows(slots)``) plus a dict tally of what the sink
    received; left out are keys of the stream that share the table's
    uint32 hash with another key of the stream."""
    import jax.numpy as jnp
    from flink_jpmml_tpu.runtime.state import COL_COUNT, COL_SUM
    from reference import gbm_ref

    faults = []
    got = sink.scores[:n_warm]
    if np.isnan(got).any():
        return [f"{int(np.isnan(got).sum())} warm-up records never delivered"]
    sample = np.arange(0, n_warm, max(1, n_warm // 4096))
    X = stream.rows(0, n_warm)[sample]
    ref = gbm_ref.scores(model_arrays, X)
    bad = ~np.isclose(got[sample], ref, rtol=SCORE_RTOL, atol=SCORE_ATOL)
    log(f"reference: {sample.size} sampled scores, max |diff| "
        f"{float(np.abs(got[sample] - ref).max()):.3e}, {int(bad.sum())} "
        f"beyond rtol {SCORE_RTOL} atol {SCORE_ATOL}")
    if bad.any():
        faults.append(f"{int(bad.sum())} scores differ from the reference")

    table = path.table
    ids = stream.ids(0, n_warm)
    tally = gbm_ref.KeyTally()
    tally.fold(ids, got)
    uniq = np.fromiter(tally.count.keys(), np.int64, len(tally.count))
    khash = table.hash_keys(uniq)
    _, inv, cnt = np.unique(khash, return_inverse=True, return_counts=True)
    shared = cnt[inv] > 1
    log(f"state: {uniq.size} distinct keys in the warm-up stream, "
        f"{int(shared.sum())} share a uint32 hash: left out")
    if shared.sum() > max(4, uniq.size // 1000):
        faults.append(f"{int(shared.sum())} keys share a hash")
    uniq, khash = uniq[~shared], khash[~shared]
    c0 = table.metrics.struct_snapshot()["counters"]
    # the table's own routing, as a lookup: every key is resident, so
    # nothing is inserted (checked) and only LRU stamps move
    slots, reset, _, _ = table.assign_slots(
        khash, np.zeros(uniq.size, np.int64)
    )
    c1 = table.metrics.struct_snapshot()["counters"]
    if c1["state_inserts"] != c0["state_inserts"] or reset.any() or (
            slots == table.scratch).any():
        faults.append("warm-up keys were not all resident in the table")
    rows = np.asarray(table.values[jnp.asarray(slots)])
    first = np.asarray(first_rows(slots), np.float64)
    want_n = first[:, COL_COUNT] + np.array(
        [tally.count[k] for k in uniq.tolist()], np.float64)
    want_s = first[:, COL_SUM] + np.array(
        [tally.total[k] for k in uniq.tolist()], np.float64)
    bad_n = rows[:, COL_COUNT] != want_n
    # float32 running sums: one rounding per record folded
    tol = 1e-6 * want_n * np.maximum(np.abs(want_s), 1.0) + 1e-4
    bad_s = np.abs(rows[:, COL_SUM] - want_s) > tol
    log(f"state: {int(bad_n.sum())} counts and {int(bad_s.sum())} score "
        f"sums differ from the tally over {uniq.size} keys (largest count "
        f"{int(want_n.max())})")
    if bad_n.any() or bad_s.any():
        faults.append("table rows differ from the reference tally")
    return faults


ZERO_COUNTERS = (
    "fallback_records", "redispatch_records", "oom_shrinks",
    "state_bypass_records", "state_rollbacks", "state_evictions",
    "state_overflow",
)
ZERO_PREFIXES = ("device_fault_total", "dlq_records")


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
    the window, under the host annotation ``bench.window``."""

    def __init__(self, jax, w0: float, after: float, seconds: float):
        self._jax = jax
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
            return xtrace.reduce_trace(xp, window_name="bench.window")
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
    child = Child()
    path = None
    planner = concurrent.futures.ThreadPoolExecutor(max_workers=1)
    try:
        child.send(
            cmd="init", seed=args.seed, topic="bench",
            n_features=cfg["model"]["n_features"],
            key_domain=cfg["key_domain"], key_mix=traffic["key_mix"],
            pool_rows=traffic["pool_rows"],
        )
        from lib import prefill

        # where the resident keys sit is numpy alone: it runs beside
        # JAX's start-up, the model and the table's allocation
        plan = planner.submit(
            prefill.plan_fill, int(cfg["resident_keys_at_start"]),
            int(cfg["table_slots"]),
        )
        jax, devices, peaks_row = require_device(cell["chips"], on_chip)

        from lib import gbm, xtrace
        from lib.stream import Stream

        compiles = []
        jax.monitoring.register_event_duration_secs_listener(
            lambda event, secs, **kw: compiles.append(event)
            if "compile" in event or "cache_retrieval" in event else None
        )
        from flink_jpmml_tpu.compile import compile_pmml
        from flink_jpmml_tpu.pmml import parse_pmml_file

        # -- set-up ----------------------------------------------------
        m = cfg["model"]
        arrays = gbm.gen_arrays(
            args.seed, m["n_trees"], m["depth"], m["n_features"],
            m["hist_bins"], m["base_score"],
        )
        with tempfile.TemporaryDirectory(prefix="bench-model-") as d:
            doc = parse_pmml_file(gbm.write_pmml(arrays, d))
        compiled = compile_pmml(doc, batch_size=int(cfg["compile_batch"]))
        log(f"model parsed and lowered at {time.monotonic() - _T_PROCESS:.1f}s")
        addr = dict(child.read(), topic="bench")
        stream = Stream(
            args.seed, m["n_features"], cfg["key_domain"], traffic["key_mix"],
            traffic["pool_rows"],
        )
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
        faults = check_warmup(
            stream, arrays, sink, path, n_warm,
            lambda slots: prefill.initial_rows(args.seed, slots),
        )
        beside += state_records()
        sink.scores = None
        log(f"warm-up checked at {time.monotonic() - _T_PROCESS:.1f}s")

        # -- the window ------------------------------------------------
        started = child.ask(cmd="start", traffic=traffic, delivered=n_warm)
        # The producer follows the sink through this thread, and the
        # harness takes the log's real lead from it: what the broker
        # holds (its reply) less what the sink has by then. A thread of
        # its own, because starting and stopping a trace blocks the main
        # one for seconds.
        leads = []  # (time, records the log is ahead of the sink)
        feeding = threading.Event()

        def feed():
            while not feeding.is_set():
                produced = child.ask(
                    cmd="delivered", n=sink.delivered_hi)["produced"]
                leads.append(
                    (time.monotonic(), produced - sink.delivered_hi))
                time.sleep(0.01)

        feedback = threading.Thread(target=feed, daemon=True)
        feedback.start()
        time.sleep(max(0.0, float(started["t0"]) + float(traffic["settle_s"])
                       - time.monotonic()))
        w0 = time.monotonic()
        w1 = w0 + float(args.seconds)
        setup_s = w0 - _T_PROCESS
        snap0 = path.metrics.struct_snapshot()
        n_compiles0 = len(compiles)
        stretch = TraceStretch(
            jax, w0, min(1.0, 0.1 * args.seconds),
            min(float(traffic["trace_seconds"]), 0.6 * args.seconds),
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
                faults.append(f"no delivery for {STALL_S:.0f} s")
            time.sleep(0.005)
        snap1 = path.metrics.struct_snapshot()
        n_compiles = len(compiles) - n_compiles0
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
        hi = sink.delivered_hi
        feeding.set()
        feedback.join(timeout=10.0)
        gen = child.ask(cmd="stop")
        path.stop()
        log(f"generator: {json.dumps(gen)}")
        in_w = [(a, t - w0) for t, a in leads if w0 <= t <= w1]
        lead = dict(zip(("least", "at_s"), min(in_w, default=(None, None))),
                    samples=len(in_w))
        log(f"log's lead over the sink, from the harness: {json.dumps(lead)}")

        # -- what the window held ---------------------------------------
        counts = sink.offset_counts(hi)
        lost, dup = int((counts == 0).sum()), int((counts > 1).sum())
        in_window = [(t, n) for _, n, t in deliveries if w0 <= t <= w1]
        attempted = int(sum(n for _, n in in_window))
        allowed = int(traffic["least_backlog_allowed"])
        for who, least in (("harness", lead["least"]),
                           ("producer", gen.get("least_backlog_records"))):
            if least is None or least < allowed:
                faults.append(
                    "the log's lead over the sink " + (
                        "was never taken" if least is None
                        else f"fell to {least} records"
                    ) + f" ({who}'s account; {allowed} allowed): the "
                    "window measured the producer"
                )
        final = path.metrics.struct_snapshot()["counters"]
        for name, v in final.items():
            if v and (name in ZERO_COUNTERS or name.startswith(ZERO_PREFIXES)):
                faults.append(f"{name} = {v}")
        # every delivered record was folded once; what was dispatched
        # and not delivered when the pipeline stopped is folded besides
        folded = final.get("state_records", 0) - beside
        slack = (int(cfg["pipeline"]["in_flight"]) + 1) * int(
            cfg["pipeline"].get("max_dispatch_chunks", 1)
        ) * int(cfg["compile_batch"])
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
            elif on_chip:
                faults.append("no operation ran on the device in the trace")
        for f in faults:
            log(f"FAULT: {f}")
        result["correct"] = not faults and failed == 0
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
        return result
    finally:
        planner.shutdown(wait=True, cancel_futures=True)
        if path is not None:
            try:
                path.stop()
            except Exception as e:  # the first error is the one to report
                log(f"stop: {type(e).__name__}: {e}")
        child.close()


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
