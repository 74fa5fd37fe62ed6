"""Example: stacked modelChain ensemble, sharded over a device mesh
(BASELINE config 5).

A MiningModel modelChain — inner GBM whose output field feeds a logistic
calibration RegressionModel — over a wide (default 10k) sparse feature
space, scored with the batch axis sharded across all available devices
(data parallelism over ICI; the reference's only parallelism is Flink
operator DP, SURVEY.md §3 P1). On a CPU host run with
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
  python examples/stacked_sharded.py
to get the virtual 8-device mesh; on a TPU slice the same code shards over
the real chips.

Run:  python examples/stacked_sharded.py [--features 10000]
"""

import argparse
import pathlib
import sys
import tempfile

try:  # installed package (pip install -e .)
    import flink_jpmml_tpu  # noqa: F401
except ImportError:  # source checkout without install: add the repo root
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

import jax
import numpy as np

from flink_jpmml_tpu.assets_gen import gen_stacked
from flink_jpmml_tpu.compile import compile_pmml
from flink_jpmml_tpu.parallel.mesh import make_mesh
from flink_jpmml_tpu.parallel.sharding import dp_sharded
from flink_jpmml_tpu.pmml import parse_pmml_file


def main() -> None:
    print(f"backend: {jax.default_backend()}")
    ap = argparse.ArgumentParser()
    ap.add_argument("--features", type=int, default=10_000)
    ap.add_argument("--trees", type=int, default=50)
    ap.add_argument("--batch", type=int, default=2048)
    args = ap.parse_args()

    workdir = tempfile.mkdtemp(prefix="fjt-stacked-")
    pmml = gen_stacked(
        workdir, n_trees=args.trees, depth=4, n_features=args.features,
        wide_lr=True,  # the full config-5 shape: GBM + wide LR + calibration
    )
    doc = parse_pmml_file(pmml)

    import jax

    from flink_jpmml_tpu.utils.config import MeshConfig

    n = len(jax.devices())
    # data x model mesh: the wide LR stage feature-shards over `model`
    n_model = 2 if n % 2 == 0 and n >= 2 else 1
    mesh = make_mesh(MeshConfig(data=n // n_model, model=n_model))
    print(f"mesh: {mesh.shape} over {n} devices")

    # mesh-aware compile: the wide stage's [F] coefficient tensors are
    # feature-sharded INSIDE the compiled scorer (GSPMD inserts the
    # tp_linear-style partial-matmul + psum); narrow params replicate
    sharded = compile_pmml(doc, mesh=mesh)
    print(f"TP-sharded param leaves: {list(sharded.tp_sharded_leaves) or '(pure-DP mesh)'}")

    rng = np.random.default_rng(0)
    # sparse-ish stream: most features zero, a few hot
    X = np.zeros((args.batch, args.features), np.float32)
    hot = rng.integers(0, args.features, size=(args.batch, 32))
    X[np.arange(args.batch)[:, None], hot] = rng.normal(
        0.0, 1.0, size=hot.shape
    )
    M = np.zeros_like(X, bool)

    out = sharded.predict(X, M)
    values = np.asarray(out.value)
    print(f"scored {args.batch} x {args.features}-dim records "
          f"(batch sharded over data, wide-LR features over model, "
          f"{mesh.shape}); "
          f"calibrated score range [{values.min():.4f}, {values.max():.4f}]")

    # plain DP on the same document stays available (params replicated)
    dp = dp_sharded(compile_pmml(doc), mesh)
    np.testing.assert_allclose(
        np.asarray(dp.predict(X, M).value), values, rtol=2e-5, atol=1e-6
    )
    print("DP-replicated predict agrees with the TP-sharded compile")


if __name__ == "__main__":
    main()
