"""Path builder ``mesh``: broker → ``KafkaBlockSource`` →
``BlockPipeline(mesh=<the cell's chips on the data axis>,
state=KeyedStateTable(mesh=...))``: one ingest, one score thread, one
state table in as many pieces as there are chips, every record routed
by key to the chip that owns its row (the program's keyed shuffle,
``runtime/shuffle.py``). Otherwise ``paths/block.py``'s shape: prefetch
on, native ring, no checkpoint directory, the key in feature column 0.

What a sharded deployment brings beside its configuration: this file
and ``warmup_checks/all_resident_sharded.py``. Here, three things
differ from the one-chip builder:

- the table is born on the chips (``KeyedStateTable(mesh=)``: zeros
  per shard, no table-sized host array) and ``fill_table`` writes its
  rows PER SHARD, each chip its own piece from ``(seed, global slot)``
  by the same function of them (``prefill._rows``), so that
  ``prefill.initial_rows(seed, slots)`` still names every row; the
  one-call ``prefill.device_table`` would put the whole table on one
  chip. Where a slot lives is the table's public ``locate`` rule, here
  through ``shard_slots`` / ``shard_rows``;
- ``warm_shapes`` loads one program a bucket size the pipeline may use
  (``BlockPipeline.bucket_chunks``), all rows on each chip's own
  scratch row at weight 0, and the renorm sweep on the sharded buffer
  (in place there: the one the window will run);
- the deployment's own sampling interval of the device profiler
  (``pipeline.device_profile_every_s``) is stated to the program
  before the pipeline is built: a sample drains the in-flight window
  of all four chips;
- every run prints each chip's share of the resident keys (when the
  table is filled) and of the records folded (when the run stops).

Every size comes from the configuration's keys (``chips``,
``compile_batch``, ``table_slots``, ``pipeline``), so ``rehearse.py``'s
tiny overrides reach all of them. With fewer devices than ``chips`` the
builder stops with a sentence.
"""

from __future__ import annotations

import importlib.util
import sys
import time

import numpy as np

from lib.pathbase import PathBase


def _program_has_the_shuffle() -> bool:
    try:
        return importlib.util.find_spec(
            "flink_jpmml_tpu.runtime.shuffle") is not None
    except ModuleNotFoundError:  # no program in this checkout at all
        return False


# Said when the harness loads the cell, before it starts a producer or
# places 320M keys: a checkout whose program cannot run this deployment
# (the parent of the PR that brought it) fails at once, with a sentence.
if not _program_has_the_shuffle():
    print("benchmark: path 'mesh': this checkout's program cannot fold a "
          "keyed state table over a mesh (it has no runtime/shuffle.py)",
          file=sys.stderr, flush=True)
    sys.exit(1)


class Path(PathBase):
    def __init__(self, cfg: dict, compiled, addr: dict, on_batch):
        """``on_batch(first_offset, n, scores, t_done)`` is the sink:
        called once per delivered dispatch, on the score thread, with
        the scores on the host and in offset order."""
        import jax

        from flink_jpmml_tpu.parallel.mesh import make_mesh
        from flink_jpmml_tpu.runtime.block import BlockPipeline
        from flink_jpmml_tpu.runtime.kafka import KafkaBlockSource
        from flink_jpmml_tpu.runtime.state import KeyedStateTable, StateSpec
        from flink_jpmml_tpu.utils.config import (
            BatchConfig, MeshConfig, RuntimeConfig,
        )
        from flink_jpmml_tpu.utils.metrics import MetricsRegistry

        self._jax = jax
        chips = int(cfg["chips"])
        devices = jax.devices()
        if len(devices) < chips:
            print(f"benchmark: path 'mesh' needs {chips} devices for this "
                  f"configuration, JAX found {len(devices)} "
                  f"({devices[0].platform}); on the CPU set XLA_FLAGS="
                  f"--xla_force_host_platform_device_count={chips}",
                  file=sys.stderr, flush=True)
            sys.exit(1)
        self.mesh = make_mesh(
            MeshConfig(data=chips, model=1), devices=devices[:chips])
        p = cfg["pipeline"]
        B = int(cfg["compile_batch"])
        self.batch = B
        self.metrics = MetricsRegistry()
        if p.get("device_profile_every_s") is not None:
            # the deployment's own sampling interval of the device
            # profiler, set before the pipeline asks for the registry's
            # profiler (a sample drains the in-flight window of all
            # four chips)
            from flink_jpmml_tpu.obs import profiler

            profiler.profiler_for(
                self.metrics, interval_s=float(p["device_profile_every_s"]))
        q = compiled.quantized_scorer()
        if q is None:
            raise RuntimeError("the model is not rank-wire eligible")
        # the scorer the pipeline binds: one twin a mesh, built once
        self.q = q.on_mesh(self.mesh)
        st = cfg["state"]
        self.table = KeyedStateTable(
            StateSpec(capacity=int(cfg["table_slots"]),
                      key_col=int(st["key_col"]), probe=int(st["probe"]),
                      decay=float(st["decay"]), stride=int(st["stride"])),
            metrics=self.metrics, mesh=self.mesh,
        )
        self._annot = jax.profiler.TraceAnnotation

        def sink(out, n, first_off):
            with self._annot("bench.sink"):
                arr = out.value if hasattr(out, "value") else out
                scores = np.asarray(arr)[:n]  # on the host, offset order
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
            mesh=self.mesh,
            state=self.table,
        )

    def facts(self) -> dict:
        t = self.table
        return {
            "kernel_backend": self.q.backend,
            "pipeline_backend": self.pipe.backend,
            "native_ring": bool(self.pipe.native),
            "kernel_layout": getattr(self.q, "layout", None),
            "chips": t.n_shards,
            "slots_a_chip": t.shard_slots,
            "rows_a_chip": t.shard_rows,
            "bucket_chunks": list(self.pipe.bucket_chunks),
        }

    def _chip_ids(self):
        return [d.id for d in self.mesh.devices[:, 0]]

    def fill_table(self, seed: int, plan: dict, log) -> None:
        """Every row written on the chip that owns it, from ``(seed,
        global slot)``; the resident keys into the host mirror
        (``prefill.apply_fill``, the global rule)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from lib import prefill

        t, axis = self.table, self.mesh.axis_names[0]
        R, Rl, cap = t.shard_slots, t.shard_rows, t.capacity

        def piece(seed_u32):
            r = jax.lax.iota(jnp.uint32, Rl)
            slot = jax.lax.axis_index(axis).astype(jnp.uint32) * jnp.uint32(
                R) + r
            live = ((r < jnp.uint32(R)) & (slot < jnp.uint32(cap)))[:, None]
            return jnp.where(
                live, prefill._rows(jnp, slot, seed_u32), jnp.float32(0.0))

        make = jax.jit(
            jax.shard_map(piece, mesh=self.mesh, in_specs=P(),
                          out_specs=P(axis, None), check_vma=False),
            out_shardings=NamedSharding(self.mesh, P(axis, None)),
        )
        # the zeros the table was born with go first: the fill holds a
        # piece-sized temporary beside its output
        t.commit(None)
        t.commit(make(np.uint32(seed & 0xFFFFFFFF)))
        prefill.apply_fill(t, plan, log)
        # positions ascend: a chip's keys are a run of them
        pos = plan["pos"]
        edges = np.searchsorted(pos, [d * R for d in range(t.n_shards + 1)])
        edges[-1] = pos.shape[0]
        share = np.diff(edges) / max(1, pos.shape[0])
        log("table fill: share of the resident keys a chip "
            f"{[round(float(s), 4) for s in share]} (hash % capacity is "
            "denser on the first part of a table that does not divide "
            "2**32)")
        jax.block_until_ready(t.values)

    def warm_shapes(self) -> None:
        """One program a bucket size, on the live table and without
        touching a key's state: the call, operand types and donation
        ``pipeline.dispatch_quantized`` makes with a shard plan, every
        row on its chip's own scratch row at weight 0; then the renorm
        sweep as the identity."""
        jax, q, t = self._jax, self.q, self.table
        F = len(q.wire.fields)
        # the pipeline stages and donates wherever the backend is not
        # the CPU's, and hands the host array over there: a rehearsal
        # has to load the program it will run, too
        donate = jax.default_backend() != "cpu"
        for K in self.pipe.bucket_chunks:
            n = t.n_shards * K * self.batch
            payload = q.wire.encode(np.zeros((n, F), np.float32))
            out, derived, S2 = q.predict_padded_state(
                q.stage(payload) if donate else payload,
                K if q.backend == "pallas" else 1, t,
                np.full(n, t.local_scratch, np.int32),
                np.zeros(n, np.float32), np.zeros(n, np.float32),
                np.zeros(n, bool), donate=donate,
            )
            t.commit(S2)
            jax.block_until_ready((out, derived, S2))
        self.warm_renorm()

    def stop(self) -> None:
        first = not self._stopped
        super().stop()
        if first:
            c = self.metrics.struct_snapshot()["counters"]
            per = [c.get(f'mesh_chip_records{{chip="{i}"}}', 0)
                   for i in self._chip_ids()]
            total = max(1.0, float(sum(per)))
            print("# records folded a chip since the start: "
                  f"{[int(v) for v in per]}, shares "
                  f"{[round(v / total, 4) for v in per]}; dispatches cut "
                  f"{int(c.get('mesh_dispatch_cuts', 0))} of "
                  f"{int(c.get('batches', 0))}", flush=True)
