"""flink_jpmml_tpu — a TPU-native streaming PMML scoring framework.

A ground-up re-design of the capability surface of ``flink-jpmml`` (a Scala
library scoring PMML models over Apache Flink data streams; see SURVEY.md) for
TPUs: a PMML→JAX transpiler lowers TreeModel, RegressionModel, NeuralNetwork,
ClusteringModel and MiningModel ensembles to ``jax.jit``-traced XLA graphs; a
micro-batching streaming runtime replaces the per-record CPU evaluator in the
hot path; keyed-stream data parallelism maps to ``shard_map``/``pjit``
sharding across a TPU mesh; and a checkpointed control stream provides dynamic
model add/remove at runtime.

Capability parity map (SURVEY.md §1, C1–C8):

- C1 PMML ingestion ........... :mod:`flink_jpmml_tpu.pmml` (parser + IR) and
                                :mod:`flink_jpmml_tpu.compile` (IR → JAX)
- C2 lazy per-worker loading .. :mod:`flink_jpmml_tpu.api.reader` (paths, not
                                models, travel; compile-once per process)
- C3 streaming evaluate API ... :mod:`flink_jpmml_tpu.api` (``Stream.evaluate``,
                                ``Stream.quick_evaluate``)
- C4 input prep/validation .... :mod:`flink_jpmml_tpu.compile.prepare`
                                (dense/sparse vectors → field tensor + masks)
- C5 total scoring ............ validity masks → ``Prediction(EmptyScore)``
                                lanes, never exceptions in the hot loop
- C6 dynamic serving .......... :mod:`flink_jpmml_tpu.serving`
- C7 fault tolerance .......... :mod:`flink_jpmml_tpu.runtime.checkpoint`
- C8 examples + assets ........ ``examples/`` and ``assets/`` at the repo root
"""

__version__ = "0.3.0"

from flink_jpmml_tpu.models.prediction import (  # noqa: F401
    EmptyScore,
    Prediction,
    Score,
    Target,
)
from flink_jpmml_tpu.models.control import (  # noqa: F401
    AddMessage,
    DelMessage,
    RolloutMessage,
    ServingMessage,
)
from flink_jpmml_tpu.rollout import GuardrailSpec  # noqa: F401
from flink_jpmml_tpu.models.core import ModelId, ModelInfo  # noqa: F401
