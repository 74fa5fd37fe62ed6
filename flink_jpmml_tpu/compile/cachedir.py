"""Where compiled programs and measured configs persist.

Two directories, both at fixed paths inside the checkout (the path is
part of the XLA cache key, so a directory that moves never hits):

- ``.jax_cache/`` — JAX's persistent compilation cache, unless
  ``JAX_COMPILATION_CACHE_DIR`` places it from outside, in which case no
  directory is set in code (jax reads the variable itself).
- ``.fjt_cache/`` — the autotune winner cache and its dependants (kernel
  cost ledger, cost-model fit, capacity model, drift baselines), unless
  ``FJT_AUTOTUNE_CACHE`` names the autotune file. A fresh checkout holds
  neither, so it compiles the built defaults and nothing outside the
  checkout chooses a kernel.
"""

from __future__ import annotations

import os
import pathlib

import jax

_ENV = "JAX_COMPILATION_CACHE_DIR"
# programs cheaper than this recompile faster than they deserialize
_MIN_COMPILE_SECS = 0.5


def checkout_root() -> pathlib.Path:
    return pathlib.Path(__file__).resolve().parents[2]


def state_dir() -> pathlib.Path:
    return checkout_root() / ".fjt_cache"


def configure_compile_cache() -> str:
    """Place the persistent compilation cache → the directory in force.
    Runs at ``flink_jpmml_tpu.compile`` import, i.e. before the first
    compile of every entry point; worker subprocesses land on the same
    directory through the environment or the same default."""
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", _MIN_COMPILE_SECS
    )
    placed = os.environ.get(_ENV)
    if placed:
        return placed
    path = str(checkout_root() / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
