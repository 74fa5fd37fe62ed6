"""Path builder ``block``: broker → ``KafkaBlockSource`` →
``BlockPipeline(state=KeyedStateTable)``, the shape ``chip_smoke.py``
proves and ``bench._measure_kafka_mode`` times (prefetch on, native
ring, no checkpoint directory). The key rides feature column 0.
"""

from __future__ import annotations

import time

import numpy as np

from lib.pathbase import PathBase



class Path(PathBase):
    def __init__(self, cfg: dict, compiled, addr: dict, on_batch):
        """``on_batch(first_offset, n, scores, t_done)`` is the sink:
        called once per delivered dispatch, on the score thread, with
        the scores already on the host."""
        import jax

        from flink_jpmml_tpu.runtime.block import BlockPipeline
        from flink_jpmml_tpu.runtime.kafka import KafkaBlockSource
        from flink_jpmml_tpu.runtime.state import KeyedStateTable, StateSpec
        from flink_jpmml_tpu.utils.config import BatchConfig, RuntimeConfig
        from flink_jpmml_tpu.utils.metrics import MetricsRegistry

        self._jax = jax
        p = cfg["pipeline"]
        B = int(cfg["compile_batch"])
        self.batch = B
        self.metrics = MetricsRegistry()
        self.q = compiled.quantized_scorer()
        if self.q is None:
            raise RuntimeError("the model is not rank-wire eligible")
        # a prebuilt table passes through BlockPipeline (block.py:427-435),
        # so the benchmark can read its rows after the warm-up stream
        st = cfg["state"]
        self.table = KeyedStateTable(
            StateSpec(capacity=int(cfg["table_slots"]),
                      key_col=int(st["key_col"]), probe=int(st["probe"]),
                      decay=float(st["decay"]), stride=int(st["stride"])),
            metrics=self.metrics,
        )
        self._annot = jax.profiler.TraceAnnotation

        def sink(out, n, first_off):
            with self._annot("bench.sink"):
                arr = out.value if hasattr(out, "value") else out
                scores = np.asarray(arr)[:n]  # the score is on the host
                on_batch(int(first_off), int(n), scores, time.monotonic())

        self.source = KafkaBlockSource(
            addr["host"], addr["port"], addr["topic"],
            n_cols=int(cfg["model"]["n_features"]),
            max_wait_ms=int(p["max_wait_ms"]), metrics=self.metrics,
        )
        self.pipe = BlockPipeline(
            self.source, compiled, sink,
            RuntimeConfig(batch=BatchConfig(
                size=B, deadline_us=int(p["deadline_us"]),
                queue_capacity=int(p["queue_capacity"]),
            )),
            metrics=self.metrics,
            in_flight=int(p["in_flight"]),
            max_dispatch_chunks=int(p["max_dispatch_chunks"]),
            prefetch=True,
            state=self.table,
        )
        self._chunks = sorted({
            k for k in (1, 2, 4, 8, 16)
            if k <= int(p["max_dispatch_chunks"])
            and k <= 1 + int(p["queue_capacity"]) // B
        })

    def facts(self) -> dict:
        return {
            "kernel_backend": self.q.backend,
            "pipeline_backend": self.pipe.backend,
            "native_ring": bool(self.pipe.native),
            "kernel_layout": getattr(self.q, "layout", None),
        }

    def warm_shapes(self) -> None:
        """Compile (or load) every program the window can run, on the
        live table and without touching a key's state: each dispatch
        size the ring can aggregate (``_aggregate_full_batches``:
        powers of two up to the ring's depth), with every row on the
        scratch slot at weight 0, and the table's renorm sweep as the
        identity. Same call, operand types and donation as
        ``pipeline.dispatch_quantized`` makes."""
        jax, q, t = self._jax, self.q, self.table
        F = len(q.wire.fields)
        for K in self._chunks:
            n = K * self.batch
            payload, k = q.pad_wire(
                q.wire.encode(np.zeros((n, F), np.float32))
            )
            out, derived, S2 = q.predict_padded_state(
                jax.device_put(payload), k, t,
                np.full(n, t.scratch, np.int32), np.zeros(n, np.float32),
                np.zeros(n, np.float32), np.zeros(n, bool), donate=True,
            )
            t.commit(S2)
            jax.block_until_ready((out, derived, S2))
        self.warm_renorm()
