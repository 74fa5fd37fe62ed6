#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

Drives the flagship path once, through the entry points a user calls:
the 500-tree depth-6 32-feature GBM (BASELINE config 2, full width,
weights from a seed) is generated, parsed, compiled at the production
batch, and scored over the Kafka wire protocol (in-process broker)
through ``BlockPipeline`` on the TPU. Every record must come back
exactly once, as a device array on a TPU device, finite, and equal to
the rank-wire XLA lowering on the whole stream and to the per-record
oracle interpreter on a sample; the pipeline's fallback, redispatch,
device-fault and dead-letter counters must all read zero.

One process, no arguments, no network. Exit 0 only if every check held;
anywhere JAX finds no TPU it exits non-zero and prints no result.
Stdout is two JSON lines: the run's facts (``{"smoke": {...}}``:
backends, records, first-compile seconds, the compile cache), then, last,
``{"ok": true, "device": {"platform", "kind", "count"}}`` and nothing
more, the device as JAX reports it.

``--mesh 4`` (a host with four chips) repeats the stream through a
``BlockPipeline`` over the model sharded on a ``data=4`` mesh and an
8-partition topic, and checks it against the single-chip scores.
"""

import argparse
import json
import os
import sys
import tempfile
import time

TREES, DEPTH, FEATURES = 500, 6, 32
BATCH = 16384
RECORDS = 4 * BATCH + 1000  # four full batches and a padded tail
ORACLE_ROWS = 48
SEED = 21
# the tolerances tests/test_qtrees_pallas.py and tests/test_qtrees.py use
RTOL, ATOL = 1e-4, 1e-5
STREAM_TIMEOUT_S = 600.0


def check(cond, msg: str) -> None:
    """An assertion that survives ``python -O``."""
    if not cond:
        raise AssertionError(msg)


def require_tpu():
    """→ (jax, device dict); exits non-zero, naming what it found,
    anywhere the default backend is not a TPU the peaks table knows."""
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        sys.exit(
            f"chip_smoke: needs a TPU backend; JAX resolved {backend!r} "
            f"with JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}"
        )
    from flink_jpmml_tpu.obs import profiler

    devices = jax.devices()
    kind = devices[0].device_kind
    if profiler.chip_peaks(kind, strict=True) is None:
        sys.exit(f"chip_smoke: no peaks on file for device kind {kind!r}")
    return jax, {
        "platform": devices[0].platform, "kind": kind, "count": len(devices),
    }


def result_line(device: dict) -> str:
    """The last line of stdout: the driver's contract is these keys and
    no others, so the run's other facts go on the line before it."""
    return json.dumps({"ok": True, "device": {
        "platform": str(device["platform"]),
        "kind": str(device["kind"]),
        "count": int(device["count"]),
    }})


def stream(jax, model, rows, n_partitions: int, platform: str) -> dict:
    """Score ``rows`` over the Kafka wire through one BlockPipeline →
    scores in row order, the output device ids, the pipeline's facts
    and its metrics struct. Row i is produced to partition i % P and
    consumed under the strict interleave, so sink offset == row index.
    ``model`` is a CompiledModel, or a ShardedModel for the mesh."""
    import numpy as np

    from flink_jpmml_tpu.runtime.block import BlockPipeline
    from flink_jpmml_tpu.runtime.kafka import KafkaBlockSource, MiniKafkaBroker
    from flink_jpmml_tpu.utils.config import BatchConfig, RuntimeConfig

    n = rows.shape[0]
    scores = np.full((n,), np.nan, np.float32)
    seen = np.zeros((n,), np.int64)
    device_sets = set()

    def sink(out, count, first_off):
        arr = out.value if hasattr(out, "value") else out
        check(isinstance(arr, jax.Array),
              f"sink got {type(arr).__name__}, not a device array")
        devs = arr.sharding.device_set
        check(all(d.platform == platform for d in devs),
              f"output lives on {sorted(d.platform for d in devs)}")
        device_sets.add(frozenset(d.id for d in devs))
        scores[first_off:first_off + count] = np.asarray(arr)[:count]
        seen[first_off:first_off + count] += 1

    broker = MiniKafkaBroker(topic="smoke", n_partitions=n_partitions)
    source = None
    try:
        broker.append_rows_round_robin(rows)
        check(broker.high_watermark == n, "broker lost records on append")
        source = KafkaBlockSource(
            broker.host, broker.port, "smoke",
            partitions=list(range(n_partitions)), n_cols=rows.shape[1],
            max_wait_ms=20, interleave="strict",
        )
        pipe = BlockPipeline(
            source, model, sink,
            RuntimeConfig(batch=BatchConfig(
                size=BATCH, deadline_us=5000,
                queue_capacity=max(65536, 4 * BATCH),
            )),
        )
        pipe.start()
        deadline = time.monotonic() + STREAM_TIMEOUT_S
        try:
            while int(seen.sum()) < n:
                check(time.monotonic() < deadline,
                      f"{int(seen.sum())}/{n} records after "
                      f"{STREAM_TIMEOUT_S:.0f}s")
                pipe.join(timeout=0.05)  # raises what a thread raised
        finally:
            pipe.stop()
        pipe.join(timeout=60.0)
        check(bool((seen == 1).all()),
              f"delivery not exactly-once: {int((seen == 0).sum())} "
              f"missing, {int((seen > 1).sum())} duplicated")
        check(bool(np.isfinite(scores).all()), "non-finite scores")
        return {
            "scores": scores,
            "device_sets": device_sets,
            "backend": pipe.backend,
            "native": pipe.native,
            "struct": pipe.metrics.struct_snapshot(),
        }
    finally:
        if source is not None:
            source.close()
        broker.close()


def check_clean_counters(struct: dict, records: int) -> None:
    """Nothing on the path fell back, retried, faulted or quarantined."""
    c = struct["counters"]
    check(c.get("records_out") == records,
          f"records_out {c.get('records_out')} != {records} sent")
    for name, v in c.items():
        if name in ("fallback_records", "redispatch_records",
                    "oom_shrinks") or name.startswith(
                        ("device_fault_total", "dlq_records")):
            check(v == 0, f"{name} = {v}, expected 0")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mesh", type=int, choices=(4,), default=0,
                    help="also run the stream over a data mesh this "
                         "wide (needs that many TPU chips in this host)")
    args = ap.parse_args(argv)
    t_start = time.monotonic()
    jax, device = require_tpu()
    check(device["count"] >= args.mesh,
          f"--mesh {args.mesh} on a host with {device['count']} chip(s)")

    import numpy as np

    from flink_jpmml_tpu.assets_gen import gen_gbm
    from flink_jpmml_tpu.compile import compile_pmml
    from flink_jpmml_tpu.compile.qtrees import build_quantized_scorer
    from flink_jpmml_tpu.pmml import parse_pmml_file
    from flink_jpmml_tpu.pmml.interp import evaluate

    # importing flink_jpmml_tpu.compile placed the compile cache
    # (compile/cachedir.py): where, and was it warm before this run?
    cache_dir = jax.config.jax_compilation_cache_dir
    cache_warm = os.path.isdir(cache_dir) and bool(os.listdir(cache_dir))

    with tempfile.TemporaryDirectory(prefix="fjt-smoke-") as model_dir:
        doc = parse_pmml_file(gen_gbm(
            model_dir, n_trees=TREES, depth=DEPTH, n_features=FEATURES,
        ))
    rng = np.random.default_rng(SEED)
    rows = rng.normal(0.0, 1.5, size=(RECORDS, FEATURES)).astype(np.float32)

    cm = compile_pmml(doc, batch_size=BATCH)
    q = cm.quantized_scorer()
    check(q is not None, "the flagship GBM is not rank-wire eligible")
    check(q.backend == "pallas", f"rank-wire kernel is {q.backend!r}")
    t0 = time.monotonic()
    jax.block_until_ready(q.predict_wire(q.wire.encode(rows[:BATCH])))
    first_compile_s = time.monotonic() - t0

    one = stream(jax, cm, rows, 1, device["platform"])
    check(one["backend"] == "rank_wire_pallas", one["backend"])
    check(one["native"] is True, "the C++ data plane did not build")
    check(len(one["device_sets"]) == 1
          and len(next(iter(one["device_sets"]))) == 1,
          f"single-chip outputs on {one['device_sets']}")
    check_clean_counters(one["struct"], RECORDS)

    # parity (a): the rank-wire XLA lowering, the whole stream
    qx = build_quantized_scorer(doc, batch_size=BATCH, backend="xla")
    check(qx is not None and qx.backend == "xla", "no XLA rank-wire twin")
    ref = np.asarray(qx.predict_wire(qx.wire.encode(rows)))[:RECORDS]
    np.testing.assert_allclose(one["scores"], ref, rtol=RTOL, atol=ATOL)
    # parity (b): the per-record oracle interpreter, a sample
    fields = doc.active_fields
    for i in np.linspace(0, RECORDS - 1, ORACLE_ROWS).astype(int):
        exp = evaluate(doc, dict(zip(fields, rows[i].tolist())))
        np.testing.assert_allclose(
            one["scores"][i], float(exp.value), rtol=RTOL, atol=ATOL,
            err_msg=f"row {i} vs the oracle interpreter",
        )

    facts = {
        "backend": jax.default_backend(),
        "kernel_backend": q.backend,
        "pipeline_backend": one["backend"],
        "native_ring": one["native"],
        "records": RECORDS,
        "first_compile_s": round(first_compile_s, 2),
        "compile_cache_dir": cache_dir,
        "compile_cache_warm": cache_warm,
    }

    if args.mesh:
        from flink_jpmml_tpu.obs import mesh as mesh_obs
        from flink_jpmml_tpu.parallel.mesh import make_mesh
        from flink_jpmml_tpu.parallel.sharding import mesh_sharded
        from flink_jpmml_tpu.utils.config import MeshConfig

        n_parts = 2 * args.mesh
        sharded = mesh_sharded(
            cm, make_mesh(MeshConfig(data=args.mesh, model=1))
        )
        many = stream(jax, sharded, rows, n_parts, device["platform"])
        check(max(len(s) for s in many["device_sets"]) == args.mesh,
              f"mesh outputs on {many['device_sets']}")
        check_clean_counters(many["struct"], RECORDS)
        chips = (mesh_obs.summary(many["struct"]) or {}).get("chips", {})
        check(len(chips) == args.mesh
              and all(c["records"] > 0 for c in chips.values()),
              f"per-chip record counters: {chips}")
        # the pipeline attached the rendezvous partition assignment
        owned = sorted(
            p for chip in sharded.assignment.chips
            for p in sharded.assignment.partitions_for(chip)
        )
        check(owned == list(range(n_parts)),
              f"partition ownership {owned}")
        # the mesh runs the one-chip rank-wire kernel on each chip's rows
        # (QuantizedScorer.on_mesh): held to the one-chip bound, and the
        # largest difference is printed (the CPU tests hold it to 0)
        np.testing.assert_allclose(
            many["scores"], one["scores"], rtol=RTOL, atol=ATOL
        )
        facts["mesh"] = {
            "chips": args.mesh,
            "partitions": n_parts,
            "pipeline_backend": many["backend"],
            "chip_records": {k: v["records"] for k, v in chips.items()},
            "max_abs_diff_to_one_chip": float(
                np.abs(many["scores"] - one["scores"]).max()
            ),
        }

    facts["elapsed_s"] = round(time.monotonic() - t_start, 1)
    print(json.dumps({"smoke": facts}), flush=True)
    print(result_line(device), flush=True)


if __name__ == "__main__":
    main()
