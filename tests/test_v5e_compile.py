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


@pytest.mark.parametrize("batch", [16384, 65536])
def test_state_fold_scatters_in_place(one_chip, no_compile_cache, batch):
    """The fold of ``benchmark/configs/gbm500_keyed.json`` (200,000,000
    slots, 6.4 GB, donated): the reset and the extrema are native
    scatters in place. The chip keeps the table column-major, tiled
    (8, 128); a scatter into one column made the compiler flatten the
    table a column at a time (``f32[1600002048]``, 9.6 GB of
    temporaries), and one into a slice of columns makes it loop over
    the records: the one ``while`` left is the five-column add
    (PERF.md §7; with it a whole-row scatter too, none is)."""
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
    assert mem.temp_size_in_bytes < 10_000_000, mem.temp_size_in_bytes
    assert f"f32[{rows * 8}]" not in text
    assert len(re.findall(r"\bwhile\(", text)) <= 1
    scatters = re.findall(r"= (\S+) scatter\(", text)
    assert len(scatters) >= 3, scatters  # reset, max, min
    assert all(s.startswith(f"f32[{rows},8]") for s in scatters), scatters
