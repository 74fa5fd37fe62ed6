"""Example: MNIST-shaped MLP NeuralNetwork scoring (BASELINE config 3).

A 784→256→10 NeuralNetwork PMML lowers to a bf16-friendly matmul chain on
the MXU (compile/neural.py); the stream carries dense pixel vectors. The
reference would walk JPMML's per-record neuron graph on the CPU.

Run:  python examples/mnist_mlp.py
"""

import pathlib
import sys
import tempfile

try:  # installed package (pip install -e .)
    import flink_jpmml_tpu  # noqa: F401
except ImportError:  # source checkout without install: add the repo root
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

import jax
import numpy as np

from flink_jpmml_tpu.assets_gen import gen_mlp
from flink_jpmml_tpu.api import ModelReader, StreamEnvironment
from flink_jpmml_tpu.utils.config import BatchConfig, RuntimeConfig


def main() -> None:
    print(f"backend: {jax.default_backend()}")
    workdir = tempfile.mkdtemp(prefix="fjt-mlp-")
    pmml = gen_mlp(workdir, n_inputs=784, hidden=(256,), n_classes=10)
    print(f"model: {pmml}")

    rng = np.random.default_rng(0)
    images = rng.uniform(0.0, 1.0, size=(512, 784)).astype(np.float32).tolist()

    env = StreamEnvironment(
        RuntimeConfig(batch=BatchConfig(size=256, deadline_us=2000))
    )
    sink = (
        env.from_collection(images)
        .quick_evaluate(ModelReader(pmml))
        .collect()
    )
    env.execute(timeout=120.0)

    preds = [p for p, _vec in sink.items]
    by_digit = {}
    for p in preds:
        by_digit[p.target.label] = by_digit.get(p.target.label, 0) + 1
    print(f"scored {len(preds)} images; class histogram: {by_digit}")
    top = preds[0]
    print(f"first image → digit {top.target.label} "
          f"(p={top.score.value:.3f})")


if __name__ == "__main__":
    main()
