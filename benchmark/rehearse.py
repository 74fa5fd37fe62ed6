#!/usr/bin/env python3
"""Rehearsals that cost no chip time. Run by hand, not part of tier-1:

    JAX_PLATFORMS=cpu python3 benchmark/rehearse.py            # every cell, tiny
    JAX_PLATFORMS=cpu python3 benchmark/rehearse.py --compile  # real size, for the v5e
    python3 benchmark/rehearse.py --starve                     # on the chip: the guard trips
    JAX_PLATFORMS=cpu python3 benchmark/rehearse.py --drain-only  # tiny: the producer against the program's bare ingest
    python3 benchmark/rehearse.py --drain-only                 # on the chip's host, real size: the same

The first runs every cell of ``BENCHMARK.json`` end to end on the CPU at
a tiny size (20 trees, 61,001 slots with 45,000 keys resident, batch
1024, a 2 s window) through ``run.run_cell``, once untraced and once
traced, and asserts the result line's keys and that a CPU run reports
no metric; then once more with the producer held, from the moment its
backlog was full, to a tenth of what the pipeline drains, and asserts
that the run is NOT correct and that ``broken`` names the log's lead;
and, on the first cell, once with the producer held from its start so
that the backlog never fills: that run has to end by itself with a
sentence, and open no window. On the CPU the rank wire scores on its
XLA twin, not on the Pallas kernel.

The second compiles the table's fill (``lib.prefill.device_table``) and
the state fold (``statekernel._state_step``) for a described
``v5e:2x2`` topology, one device, at each configuration's real table
size and batch, and prints ``memory_analysis()``: what the 6.4 GB
buffer, its donation and the scatters cost is known before chip time is
spent. Nothing runs; it is not a chip run.

The third is a chip run (four chips: it runs every cell in one
process): every saturated cell at its real size for 10 s with the
producer held to ``STARVED_RECORDS_PER_S``, well under what the
pipeline drains, once its backlog has been full. It has to end
``correct: false`` with the log's lead under ``broken``, and exits
non-zero if it does not; then the first cell with the producer held
from its start, which has to end with the sentence and no window.

The fourth needs no device: each cell's producer (``lib/loadgen.py``,
the cell's own traffic file) against a consumer that does nothing but
drain: the cell's ``KafkaBlockSource`` behind the program's prefetch
sidecar, its blocks counted and dropped. No pipeline fed through that
source can take the log faster, so where the log's lead over this
consumer never falls under ``least_backlog_allowed`` (by the harness's
account and the producer's, as in a run), no window of the cell can
"measure the producer". Three seeds, ``run_seconds`` each at real size;
one seed of 2 s at the tiny size under ``JAX_PLATFORMS=cpu``. Every
offset has to arrive once, in order, and the head of every block has to
be the stream's. Exits non-zero where the lead is not held.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

TINY = {
    "cfg": {
        "model": {"n_trees": 20},
        "table_slots": 61001,  # not a power of two: keys are displaced, one wraps
        "key_domain": 45000,
        "resident_keys_at_start": 45000,
        "compile_batch": 1024,
        "pipeline": {"queue_capacity": 4096},
        "warmup_records": 4196,
    },
    "traffic": {
        "backlog_records": 131072, "least_backlog_allowed": 32768,
        "chunk_records": 8192, "pool_rows": 512,
        "settle_s": 0.5, "trace_seconds": 0.5,
    },
}
# held from the moment the backlog was first full: the window opens on a
# full log and the pipeline drains it
TINY_STARVED = {
    "cfg": TINY["cfg"],
    "traffic": dict(TINY["traffic"], producer_max_records_per_s=20000,
                    producer_max_from="filled"),
}
# held from its start: the backlog never fills and no window opens
TINY_NEVER_FULL = {
    "cfg": TINY["cfg"],
    "traffic": dict(TINY["traffic"], producer_max_records_per_s=2000),
}
STARVED_RECORDS_PER_S = 40000
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def cells():
    import run

    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as fh:
        return [w["name"] for w in json.load(fh)["workloads"]]


def expect_starved(cell: str, res: dict) -> None:
    line = json.dumps(res)
    print(line, flush=True)
    if res["correct"] or res["failed"]:
        sys.exit(f"{cell}: a starved run has to end correct: false with "
                 f"nothing failed: {line}")
    if not any(n.startswith("least_lead_records.") for n in res["broken"]):
        sys.exit(f"{cell}: a starved run's `broken` has to name the log's "
                 f"lead: {res['broken']}")


def expect_no_window(cell: str, seconds: float, overrides: dict,
                     on_chip: bool) -> None:
    """The cell under ``overrides`` that hold its producer so that the
    backlog never fills: the run has to stop by itself (``run.die``),
    not measure."""
    import run

    args = argparse.Namespace(
        workload=cell, seed=2**31 + 11, seconds=seconds, trace=0)
    try:
        res = run.run_cell(args, overrides=overrides, on_chip=on_chip)
    except SystemExit as stopped:
        if stopped.code == 1:
            print(f"{cell}: a producer that never filled its backlog ended "
                  "the run before any window", flush=True)
            return
        raise
    sys.exit(f"{cell}: a producer held under its backlog got a window: "
             f"{json.dumps(res)[:2000]}")


def rehearse_cells() -> None:
    import run

    for cell in cells():
        for trace in (0, 1):
            args = argparse.Namespace(
                workload=cell, seed=2**31 + 11, seconds=2.0, trace=trace
            )
            res = run.run_cell(args, overrides=TINY, on_chip=False)
            line = json.dumps(res)
            print(line, flush=True)
            missing = RESULT_KEYS - set(res)
            if missing:
                sys.exit(f"{cell}: result lacks {sorted(missing)}")
            if res["metrics"]:
                sys.exit(f"{cell}: a CPU run printed metrics")
            if res["device"]["platform"] == "tpu":
                sys.exit("rehearse.py is for the CPU; use run.py on the chip")
            if not res["correct"] or res["failed"]:
                sys.exit(f"{cell} trace={trace}: not correct: {line}")
        args = argparse.Namespace(
            workload=cell, seed=2**31 + 11, seconds=2.0, trace=0
        )
        expect_starved(cell, run.run_cell(
            args, overrides=TINY_STARVED, on_chip=False))
    expect_no_window(cells()[0], 2.0, TINY_NEVER_FULL, on_chip=False)
    print("rehearsal: every cell ran end to end on the CPU, failed when its "
          "producer was held back, and got no window from a producer that "
          "never filled its backlog", flush=True)


def starve_on_chip() -> None:
    import run

    for cell in cells():
        args = argparse.Namespace(
            workload=cell, seed=2**31 + 11, seconds=10.0, trace=0
        )
        expect_starved(cell, run.run_cell(args, overrides={"traffic": {
            "producer_max_records_per_s": STARVED_RECORDS_PER_S,
            "producer_max_from": "filled"}}))
    expect_no_window(cells()[0], 10.0, {"traffic": {
        "producer_max_records_per_s": STARVED_RECORDS_PER_S}}, on_chip=True)
    print("starved: every cell failed when its producer was held back, and "
          "a producer that never filled its backlog got no window",
          flush=True)


def drain_only(tiny: bool) -> None:
    import threading
    import time

    import numpy as np

    import run
    from flink_jpmml_tpu.runtime import prefetch
    from flink_jpmml_tpu.runtime.kafka import KafkaBlockSource
    from flink_jpmml_tpu.utils.metrics import MetricsRegistry
    from lib import cores
    from lib.stream import Stream

    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as fh:
        seconds = 2.0 if tiny else float(json.load(fh)["run_seconds"])
    seeds = (2**31 + 11,) if tiny else (2**31 + 11, 2**31 + 77, 2**31 + 1234)
    for cell in cells():
        for seed in seeds:
            args = argparse.Namespace(
                workload=cell, seed=seed, seconds=seconds, trace=0)
            _, cfg, traffic, _, _, _ = run.load_cell(
                args, TINY if tiny else None)
            F = int(cfg["model"]["n_features"])
            stream = Stream(seed, F, cfg["key_domain"], traffic["key_mix"],
                            traffic["pool_rows"])
            split = cores.split(cores.allowed())
            child = run.Child()
            drained = {"hi": 0, "bad": []}
            stop = threading.Event()
            source = None
            try:
                child.send(cmd="init", seed=seed, topic="bench", n_features=F,
                           key_domain=cfg["key_domain"],
                           key_mix=traffic["key_mix"],
                           pool_rows=traffic["pool_rows"],
                           cores=split["producer"])
                addr = child.read()
                source = prefetch.maybe_wrap_block(
                    KafkaBlockSource(
                        addr["host"], addr["port"], "bench", n_cols=F,
                        max_wait_ms=int(cfg["pipeline"]["max_wait_ms"]),
                        metrics=MetricsRegistry(),
                    ), enable=True)

                def drain():
                    while not stop.is_set():
                        item = source.poll()
                        if item is None:
                            continue
                        first, rows = item
                        if first != drained["hi"]:
                            drained["bad"].append(
                                f"block at {first}, expected {drained['hi']}")
                        elif not np.array_equal(
                                rows[:64], stream.rows(first, first + min(
                                    64, rows.shape[0]))):
                            drained["bad"].append(f"rows at {first} differ")
                        drained["hi"] = first + rows.shape[0]

                consumer = threading.Thread(target=drain, daemon=True)
                consumer.start()
                started = child.ask(cmd="start", traffic=traffic, delivered=0)
                watch = run.LeadWatch(child, lambda: drained["hi"])
                full_after = watch.wait_for_backlog(
                    started, traffic, lambda: None)
                w0, n0 = time.monotonic(), drained["hi"]
                time.sleep(seconds)
                w1, n1 = time.monotonic(), drained["hi"]
                watch.stop()
                gen = child.ask(cmd="stop")
                stop.set()
                consumer.join(timeout=10.0)
            finally:
                stop.set()
                if source is not None:
                    source.close()
                child.close()
            lead = watch.least(w0, w1)
            allowed = int(traffic["least_backlog_allowed"])
            res = {
                "cell": cell, "seed": seed, "window_s": w1 - w0,
                "producer_cores": len(split["producer"]),
                "backlog_full_after_s": full_after,
                "drained_records_per_s": (n1 - n0) / (w1 - w0),
                "least_lead_harness": lead, "generator": gen,
                "allowed": allowed, "faults": drained["bad"][:5],
            }
            print(json.dumps(res), flush=True)
            least = (lead["least"], gen.get("least_backlog_records"))
            if drained["bad"] or any(v is None or v < allowed for v in least):
                sys.exit(f"{cell}: the producer did not hold the log's lead "
                         f"over a consumer that only drains: {least}, "
                         f"{allowed} allowed")
    print("drain-only: the producer held the log's lead over the program's "
          "bare ingest in every cell", flush=True)


def compile_for_v5e() -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import run

    sys.path.insert(0, run.ROOT)
    from flink_jpmml_tpu.compile import statekernel
    from lib import prefill

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as fh:
        configs = json.load(fh)["configs"]
    for c in configs:
        with open(os.path.join(run.ROOT, c["file"])) as fh:
            cfg = json.load(fh)
        cap, B = int(cfg["table_slots"]), int(cfg["compile_batch"])
        # rows as KeyedStateTable pads them (capacity + scratch, to 256)
        rows = -(-(cap + 1) // 256) * 256

        def fold(S, score, slots, rel, w, reset):
            return statekernel._state_step(
                S, score, slots, rel, w, reset, cap, 0.999
            )

        def sds(shape, dt):
            return jax.ShapeDtypeStruct(shape, dt, sharding=one)

        def report(what, compiled):
            ma = compiled.memory_analysis()
            print(f"{c['name']}: {what}: arguments "
                  f"{ma.argument_size_in_bytes / 1e9:.2f} GB, aliased "
                  f"{ma.alias_size_in_bytes / 1e9:.2f} GB, temporaries "
                  f"{ma.temp_size_in_bytes / 1e9:.2f} GB, output "
                  f"{ma.output_size_in_bytes / 1e9:.2f} GB", flush=True)

        report(f"table fill [{rows}, 8] f32", prefill.table_program(
            rows, cap).lower(sds((), jnp.uint32)).compile())
        for donate in (True, False):
            try:
                compiled = jax.jit(
                    fold, donate_argnums=(0,) if donate else ()
                ).lower(
                    sds((rows, 8), jnp.float32), sds((B,), jnp.float32),
                    sds((B,), jnp.int32), sds((B,), jnp.float32),
                    sds((B,), jnp.float32), sds((B,), jnp.bool_),
                ).compile()
                report(f"state fold [{rows}, 8] f32, batch {B}, "
                       f"donated={donate}", compiled)
            except Exception as e:  # the compiler's refusal is the finding
                print(f"{c['name']}: donated={donate}: REFUSED "
                      f"{type(e).__name__}: {str(e)[:400]}", flush=True)


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--compile", action="store_true")
    ap.add_argument("--starve", action="store_true")
    ap.add_argument("--drain-only", action="store_true")
    a = ap.parse_args()
    if a.starve:
        starve_on_chip()
    elif a.drain_only:
        drain_only(tiny=os.environ.get("JAX_PLATFORMS") == "cpu")
    elif os.environ.get("JAX_PLATFORMS") != "cpu":
        sys.exit("rehearse.py runs under JAX_PLATFORMS=cpu")
    else:
        compile_for_v5e() if a.compile else rehearse_cells()
