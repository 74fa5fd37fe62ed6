"""Programs of the hot path compiled for a described TPU v5e, at the
size a deployment runs them. No chip and no times: what the chip's
compiler makes of a program (its buffers, its loops) is read off the
compiled text, so a lowering that costs O(table) a dispatch is caught
here and not on the chip.

One file on purpose: only the pytest worker that is handed it loads the
TPU compiler, and it does so inside a fixture, never at import."""

import os
import re

import pytest


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described device is written to the persistent
    cache and can never be read back: keep it out."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _table_accesses(text, rows):
    """``(gathers, scatters)`` of a compiled program's text whose
    operand is a ``[rows, 8]`` table, each by its result shape. The
    fold's small permutations (a dispatch's operands put in slot order,
    its derived rows put back) are gathers and scatters of ``[B]`` and
    never of the table."""
    table = f"f32[{rows},8]"
    shape_of = dict(re.findall(r"(%[\w.\-]+) = (\S+) ", text))
    hits = re.findall(r"= (\S+) (gather|scatter)\((%[\w.\-]+),", text)
    return tuple(
        [res for res, op, operand in hits
         if op == kind and shape_of.get(operand, "").startswith(table)]
        for kind in ("gather", "scatter")
    )


@pytest.mark.parametrize("batch", [16384, 65536])
def test_state_fold_reads_and_writes_the_table_once(
        one_chip, no_compile_cache, batch):
    """The fold of ``benchmark/configs/gbm500_keyed.json`` (200,000,000
    slots, 6.4 GB, donated): ONE gather reads the table and ONE native
    scatter of whole rows writes it, in place (PR 35: the dispatch is
    grouped by slot on the chip; until then a reset, an add, a max and
    a min each walked the table). The chip keeps the table
    column-major, tiled (8, 128); a scatter into one column made the
    compiler flatten the table a column at a time
    (``f32[1600002048]``, 9.6 GB of temporaries), and one into a slice
    of columns made it loop over the records (the five-column add
    until PR 28: 3.57 µs a record). No ``while`` is in the program."""
    import jax
    import jax.numpy as jnp

    from flink_jpmml_tpu.compile import statekernel

    cap = 200_000_000
    rows = -(-(cap + 1) // 256) * 256  # as KeyedStateTable pads them

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    compiled = jax.jit(
        lambda S, *a: statekernel._state_step(S, *a, cap, 0.999),
        donate_argnums=(0,),
    ).lower(
        sds((rows, 8), jnp.float32), sds((batch,), jnp.float32),
        sds((batch,), jnp.int32), sds((batch,), jnp.float32),
        sds((batch,), jnp.float32), sds((batch,), jnp.bool_),
    ).compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    table_bytes = rows * 8 * 4
    assert mem.alias_size_in_bytes >= table_bytes
    # the two row gathers that permute a dispatch take their [B, 4]
    # and [B, 8] operands row-major, a row padded to 128 lanes: 33.5 MB
    # at 65,536 records, and the largest thing the fold allocates
    assert mem.temp_size_in_bytes < table_bytes // 100, mem.temp_size_in_bytes
    assert f"f32[{rows * 8}]" not in text
    assert not re.findall(r"\bwhile\(", text)
    gathers, scatters = _table_accesses(text, rows)
    assert len(gathers) == 1, gathers
    assert len(scatters) == 1, scatters


@pytest.fixture(scope="module")
def mesh_2x2():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return Mesh(
        np.asarray(topo.devices).reshape(4, 1), ("data", "model")
    )


def test_mesh_state_program_keeps_the_table_on_its_chip(
        mesh_2x2, no_compile_cache, tmp_path):
    """The state-armed entry over four chips, as
    ``statekernel.entry_for`` builds it for a scorer on a mesh, at the
    table of ``benchmark/configs/gbm500_keyed_mesh4.json`` (560,000,000
    slots: 140,000,256 rows, 4.48 GB a chip): every chip folds its own
    piece in place, with ONE gather that reads the piece and ONE scatter
    that writes it (each chip sorts its own bucket by slot: the sort
    is local). No collective is in the program (so none touches the
    table), no ``while`` is in it nor in its one-chip twin (every
    table write is a native scatter: a loop would walk a bucket's pad
    rows as it walks records), the donated table is aliased shard by
    shard and the temporaries stay under 1% of a shard. The forest here
    is the XLA rank-wire twin of a small model (the Pallas kernel is
    built only where a TPU is attached); the fold is the deployment's."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from flink_jpmml_tpu.assets_gen import gen_gbm
    from flink_jpmml_tpu.compile import compile_pmml, statekernel
    from flink_jpmml_tpu.pmml import parse_pmml_file
    from flink_jpmml_tpu.runtime.state import KeyedStateTable, StateSpec

    batch, cap, D = 16384, 560_000_000, 4
    q = compile_pmml(
        parse_pmml_file(gen_gbm(str(tmp_path), n_trees=5, depth=3,
                                n_features=4)), batch_size=batch,
    ).quantized_scorer()
    # the table's own layout rule, without the table's arrays
    layout = KeyedStateTable.__new__(KeyedStateTable)
    layout.capacity = cap
    layout._set_layout(D)
    assert layout.shard_rows == 140_000_256
    # a twin on the described mesh (on_mesh would device_put: a
    # described device holds nothing)
    twin = dataclasses.replace(
        q, mesh=mesh_2x2, _multi_fns={}, _donate_fn=None, _mesh_twins={}
    )
    repl, data = twin.shardings()

    def sds(shape, dt, sharding):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    def lowered(scorer, rows, n, scratch, params_at, at):
        fn = statekernel.entry_for(scorer, "wire", 1, True, 0.999, scratch)
        params = jax.tree_util.tree_map(
            lambda a: sds(a.shape, a.dtype, params_at), scorer.params)
        return fn.lower(
            params, sds((n, 4), jnp.uint8, at),
            sds((rows, 8), jnp.float32, at), sds((n,), jnp.int32, at),
            sds((n,), jnp.float32, at), sds((n,), jnp.float32, at),
            sds((n,), jnp.bool_, at),
        ).compile()

    mesh_prog = lowered(twin, D * layout.shard_rows, D * batch,
                        layout.local_scratch, repl, data)
    from jax.sharding import SingleDeviceSharding

    one = SingleDeviceSharding(mesh_2x2.devices[0, 0])
    one_prog = lowered(
        dataclasses.replace(q, _multi_fns={}), layout.shard_rows, batch,
        layout.local_scratch, one, one)
    text, one_text = mesh_prog.as_text(), one_prog.as_text()
    assert "jit_state_fn" in text.split("\n", 1)[0]
    for collective in ("all-gather", "all-reduce", "all-to-all",
                       "collective-permute", "reduce-scatter"):
        assert collective not in text, collective
    assert not re.findall(r"\bwhile\(", text)
    assert not re.findall(r"\bwhile\(", one_text)
    mem, shard_bytes = mesh_prog.memory_analysis(), layout.shard_rows * 8 * 4
    assert mem.alias_size_in_bytes >= shard_bytes
    assert mem.temp_size_in_bytes < shard_bytes // 100, mem.temp_size_in_bytes
    assert f"f32[{layout.shard_rows},8]" in text  # a chip's own piece
    assert f"f32[{D * layout.shard_rows},8]" not in text
    for prog in (text, one_text):
        gathers, scatters = _table_accesses(prog, layout.shard_rows)
        assert (len(gathers), len(scatters)) == (1, 1), (gathers, scatters)


def test_mesh_renorm_sweeps_every_piece_in_place(mesh_2x2, no_compile_cache):
    """The renorm of a table over four chips, as ``statekernel.renorm``
    runs it there (donated): each chip's piece (140,000,256 rows,
    4.48 GB) is its own output, so the table never moves — a piece that
    moved changed its chip's pace (PERF.md §6, PR 27) — and no chip
    holds a second copy or talks to another."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from flink_jpmml_tpu.compile import statekernel

    rows = 4 * 140_000_256
    at = NamedSharding(mesh_2x2, P("data", None))
    repl = NamedSharding(mesh_2x2, P())
    compiled = statekernel.renorm_program(True).lower(
        jax.ShapeDtypeStruct((rows, 8), jnp.float32, sharding=at),
        jax.ShapeDtypeStruct((8,), jnp.float32, sharding=repl),
        jax.ShapeDtypeStruct((8,), jnp.float32, sharding=repl),
    ).compile()
    mem, shard_bytes = compiled.memory_analysis(), rows // 4 * 8 * 4
    assert mem.alias_size_in_bytes >= shard_bytes
    assert mem.temp_size_in_bytes < shard_bytes // 100, mem.temp_size_in_bytes
    text = compiled.as_text()
    for collective in ("all-gather", "all-reduce", "all-to-all",
                       "collective-permute", "reduce-scatter"):
        assert collective not in text, collective
