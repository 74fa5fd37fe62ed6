"""Test harness: force an 8-device virtual CPU mesh (SURVEY.md §5).

The reference tested "distributed" behavior on Flink's in-process MiniCluster;
our equivalent is a single-process 8-device CPU JAX runtime — sharding tests
exercise real ``Mesh``/``shard_map`` code paths without TPU hardware. Must run
before the first ``import jax`` anywhere in the test session.

``JAX_PLATFORMS`` is only defaulted, never forced: ``JAX_PLATFORMS=tpu python
-m pytest tests/test_compile_golden.py`` runs a golden suite against the real
chip's numerics (multi-device tests need the virtual CPU mesh and should be
deselected then).
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import pathlib
import sys
import tempfile

# Make the repo root importable regardless of how pytest is invoked.
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

# Hermetic rank-wire autotune cache (compile/autotune.py):
# build_quantized_scorer consults it on EVERY compile, including ones
# inside class/session-scoped fixtures that run before any
# function-scoped monkeypatch — so the redirect must happen at conftest
# import, unconditionally (the checkout's .fjt_cache entry would
# otherwise silently switch golden models to tuned configs per machine).
os.environ["FJT_AUTOTUNE_CACHE"] = os.path.join(
    tempfile.mkdtemp(prefix="fjt-test-autotune-"), "autotune.json"
)

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _isolated_autotune_cache(tmp_path, monkeypatch):
    """Per-test cache file on top of the import-time session redirect
    above: one test's sweep must not leak tuned configs into another's
    compiles (higher-scoped fixtures still use the session file)."""
    monkeypatch.setenv("FJT_AUTOTUNE_CACHE", str(tmp_path / "autotune.json"))


@pytest.fixture(scope="session")
def assets_dir(tmp_path_factory):
    """Generated PMML fixtures shared across the test session."""
    from assets.generate import generate_all

    out = tmp_path_factory.mktemp("pmml_assets")
    generate_all(str(out))
    return out
