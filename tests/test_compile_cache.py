"""Where the persistent compile cache lands (compile/cachedir.py): placed
from outside by ``JAX_COMPILATION_CACHE_DIR``, else at the fixed
``<checkout>/.jax_cache`` — a path that moves never hits."""

import os
import pathlib
import subprocess
import sys

import pytest

from flink_jpmml_tpu.compile import autotune, cachedir

REPO = pathlib.Path(__file__).resolve().parent.parent


def test_default_is_fixed_inside_the_checkout(monkeypatch):
    import jax

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prev = jax.config.jax_compilation_cache_dir
    try:
        path = cachedir.configure_compile_cache()
        assert path == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_env_placement_sets_no_directory_in_code(monkeypatch, tmp_path):
    import jax

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    prev = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", "sentinel")
        assert cachedir.configure_compile_cache() == str(tmp_path)
        # untouched: jax reads the variable itself at start-up
        assert jax.config.jax_compilation_cache_dir == "sentinel"
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_autotune_state_defaults_next_to_it(monkeypatch):
    monkeypatch.delenv("FJT_AUTOTUNE_CACHE", raising=False)
    assert autotune.cache_path() == REPO / ".fjt_cache" / "autotune.json"
    monkeypatch.setenv("FJT_AUTOTUNE_CACHE", "/x/at.json")
    assert autotune.cache_path() == pathlib.Path("/x/at.json")


_PROG = """
import tempfile, time
import jax
from flink_jpmml_tpu.assets_gen import gen_gbm
from flink_jpmml_tpu.compile import compile_pmml
from flink_jpmml_tpu.pmml import parse_pmml_file
# the production threshold skips trivial compiles; persist everything
# for this tiny test model
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
print("CACHE_DIR=" + str(jax.config.jax_compilation_cache_dir))
d = tempfile.mkdtemp()
doc = parse_pmml_file(gen_gbm(d, n_trees=20, depth=4, n_features=6))
compile_pmml(doc, batch_size=256).warmup()
"""


def _run(env):
    r = subprocess.run(
        [sys.executable, "-c", _PROG], env=env, capture_output=True,
        text=True, timeout=240,
    )
    assert r.returncode == 0, r.stderr[-800:]
    return r.stdout.split("CACHE_DIR=", 1)[1].splitlines()[0]


@pytest.mark.slow  # subprocess compile drill
def test_child_compile_lands_where_the_variable_says(tmp_path):
    cache = tmp_path / "placed"
    env = dict(
        os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO),
        JAX_COMPILATION_CACHE_DIR=str(cache),
    )
    assert _run(env) == str(cache)
    assert os.listdir(cache), "no compile landed in the placed directory"


@pytest.mark.slow  # subprocess compile drill
def test_unset_default_is_identical_in_successive_processes():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    first, second = _run(env), _run(env)
    assert first == second == str(REPO / ".jax_cache")
