"""Sharded scoring: DP over the batch axis, 1-D TP over wide feature dims.

Reference parity (SURVEY.md §3 P1–P3): Flink ran N subtasks each holding a
model copy; here one jitted computation spans the mesh —

- :func:`dp_sharded` re-jits any :class:`CompiledModel` with the micro-batch
  sharded over the ``data`` axis and params replicated. XLA partitions the
  whole scoring graph; no collectives are needed on the forward path (the
  batch axis is embarrassingly parallel), so scaling rides ICI bandwidth
  only for the input scatter / output gather.
- :func:`tp_linear` is the building block for BASELINE config 5: a wide
  linear transform whose feature dimension is sharded over the ``model``
  axis via ``shard_map`` — each device holds a column-slice of W and a
  feature-slice of X, computes a partial matmul, and ``psum`` combines
  partials over ICI (the scaling-book 1-D tensor-parallel recipe).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from flink_jpmml_tpu.compile.common import HIGHEST, ModelOutput
from flink_jpmml_tpu.compile.compiler import CompiledModel
from flink_jpmml_tpu.obs import recorder as flight
from flink_jpmml_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS
from flink_jpmml_tpu.utils.exceptions import (
    FlinkJpmmlTpuError,
    InputValidationException,
)

@dataclass
class ShardedModel:
    """A CompiledModel re-jitted for a mesh: same predict contract, batch
    sharded over ``data``; params replicated (:func:`dp_sharded`) or
    feature-sharded over ``model`` where wide (:func:`mesh_sharded`)."""

    base: CompiledModel
    mesh: Mesh
    _jit_fn: object
    _params_sharded: object
    # names of param leaves sharded over the model axis ("" = none):
    # observability for tests/dryruns asserting the TP path is real
    tp_sharded_leaves: tuple = ()
    # hot-path serving state carried THROUGH a degraded-mesh rebuild
    # (ISSUE 16 satellite: callers used to re-derive both by hand):
    # - dispatch_state: the dispatcher/window geometry the pipelines
    #   configured (in-flight depth, donation, staging knobs) — opaque
    #   dict, copied verbatim onto the rebuilt model;
    # - assignment: the ChipAssignment (parallel/assignment.py) mapping
    #   kafka partitions / record keys to chips — re-balanced with
    #   ``.without(lost)`` so only the dead chip's work moves.
    dispatch_state: Optional[dict] = None
    assignment: object = None

    @property
    def batch_divisor(self) -> int:
        return self.mesh.shape[DATA_AXIS]

    def in_flight_depth(self, base_depth: int) -> int:
        """Mesh-aware in-flight window: the carried dispatch_state's
        depth when one was configured, else the data-width heuristic
        (parallel/assignment.mesh_in_flight)."""
        from flink_jpmml_tpu.parallel.assignment import mesh_in_flight

        ds = self.dispatch_state or {}
        if "in_flight" in ds:
            return int(ds["in_flight"])
        return mesh_in_flight(self.mesh, base_depth)

    def with_dispatch_state(self, **kv) -> "ShardedModel":
        """Attach/merge dispatcher-window state (returns self — the
        pipelines call this at bind time; dataclass stays mutable by
        design, mirroring how _params_sharded is owned)."""
        ds = dict(self.dispatch_state or {})
        ds.update(kv)
        self.dispatch_state = ds
        return self

    def predict(self, X, M) -> ModelOutput:
        if X.shape[0] % self.batch_divisor != 0:
            raise InputValidationException(
                f"sharded batch {X.shape[0]} must divide by the data-axis "
                f"size {self.batch_divisor} (pad the micro-batch)"
            )
        return self._jit_fn(self._params_sharded, X, M)

    def decode(self, out: ModelOutput, n: Optional[int] = None):
        return self.base.decode(out, n)

    def warmup(self) -> "ShardedModel":
        b = self.base.batch_size or self.batch_divisor
        b += (-b) % self.batch_divisor
        X = np.zeros((b, self.field_space.arity), np.float32)
        M = np.zeros((b, self.field_space.arity), bool)
        jax.block_until_ready(self.predict(X, M))
        return self

    # -- convenience wrappers (CompiledModel parity for serving/tests) ----

    def score_records(self, records):
        from flink_jpmml_tpu.compile import prepare

        X, M = prepare.from_records(self.field_space, records)
        return self._score(X, M, n=X.shape[0])

    def score_dense(self, vectors, replace_nan: Optional[float] = None):
        from flink_jpmml_tpu.compile import prepare

        X, M = prepare.from_dense(self.field_space, vectors, replace_nan)
        return self._score(X, M, n=X.shape[0])

    def _score(self, X, M, n: int):
        from flink_jpmml_tpu.compile import prepare

        target = self.base.batch_size or X.shape[0]
        target += (-target) % self.batch_divisor  # mesh-divisible pad
        X, M, _ = prepare.pad_batch(X, M, target)
        return self.decode(self.predict(X, M), n)

    def quantized_scorer(self):
        """The base model's rank-wire scorer over this mesh
        (``QuantizedScorer.on_mesh``): forest parameters replicated,
        the wire batch sharded on the data axis, each chip running the
        one-chip kernel on its rows, so scores are the one-chip
        scorer's bit for bit. None where the base model has none (the
        BlockPipeline fallback contract: it then scores on the f32
        path)."""
        q = self.base.quantized_scorer()
        return None if q is None else q.on_mesh(self.mesh)

    @property
    def field_space(self):
        return self.base.field_space

    @property
    def batch_size(self):
        return self.base.batch_size

    @property
    def labels(self):
        return self.base.labels

    @property
    def is_classification(self):
        return self.base.is_classification

    @property
    def model_name(self):
        return self.base.model_name

    @property
    def output_fields(self):
        return self.base.output_fields

    @property
    def active_fields(self):
        return self.base.active_fields

    @property
    def _verification(self):
        return self.base._verification

    @property
    def _target_field(self):
        return self.base._target_field

    @property
    def has_verification(self) -> bool:
        return self.base.has_verification

    def verify(self):
        """Replay embedded <ModelVerification> vectors through the
        SHARDED jit — the computation that will actually serve. The
        GSPMD re-jit (in/out shardings, TP partitioning of wide leaves)
        is precisely the kind of transformation the vectors exist to
        validate; delegating to the unsharded base would check a code
        path the sharded model never uses."""
        from flink_jpmml_tpu.compile.verify import run_verification

        return run_verification(self, self.base._target_field)

    def without_devices(self, lost) -> "ShardedModel":
        """Degraded-mesh mode (ROADMAP item 1): rebuild this model
        over the mesh MINUS ``lost`` — the recovery move for an
        unrecoverable ``chip_loss`` (runtime/devfault.py). The DrJAX
        map/reduce framing is what makes this a small operation:
        per-chip state already fleet-merges exactly, so a mesh minus
        one chip is just a smaller fleet — params re-place onto the
        survivors from the host copy, the batch divisor shrinks, and
        the scoring contract is unchanged. TP sharding is preserved
        when the survivor count still honours the model axis
        (:func:`degraded_mesh`).

        Serving state CARRIES THROUGH the rebuild: the dispatcher/
        window geometry (``dispatch_state``) copies verbatim, and the
        partition/key assignment re-balances via ``assignment
        .without(lost)`` — only the dead chip's partitions and keys
        move (rendezvous hashing), so healthy chips keep their kafka
        partitions and canary slices with zero re-derivation by the
        caller."""
        new_mesh = degraded_mesh(self.mesh, lost)
        if self.tp_sharded_leaves:
            rebuilt = mesh_sharded(self.base, new_mesh)
        else:
            rebuilt = dp_sharded(self.base, new_mesh)
        if self.dispatch_state is not None:
            rebuilt.dispatch_state = dict(self.dispatch_state)
        if self.assignment is not None:
            rebuilt.assignment = self.assignment.without(lost)
        flight.record(
            "mesh_degraded",
            lost=[str(getattr(d, "id", d)) for d in lost],
            data=new_mesh.shape[DATA_AXIS],
            model=new_mesh.shape[MODEL_AXIS],
        )
        return rebuilt


def degraded_mesh(mesh: Mesh, lost) -> Mesh:
    """→ the ``data × model`` mesh over ``mesh``'s devices minus
    ``lost`` (devices or device ids). The MODEL axis width is
    preserved — TP shards partition param tensors, so shrinking that
    axis would change the program; the DATA axis absorbs the loss
    (shards re-balance onto survivors). Survivors that no longer fill
    a whole data row are trimmed (idle beats wrong). Raises when no
    full data row survives."""
    lost_ids = {getattr(d, "id", d) for d in lost}
    survivors = [
        d for d in mesh.devices.flat
        if getattr(d, "id", d) not in lost_ids
    ]
    n_model = mesh.shape[MODEL_AXIS]
    data = len(survivors) // n_model
    if data < 1:
        raise FlinkJpmmlTpuError(
            f"degraded mesh unsurvivable: {len(survivors)} device(s) "
            f"left cannot fill one {n_model}-wide model-axis row"
        )
    grid = np.asarray(survivors[: data * n_model]).reshape(data, n_model)
    return Mesh(grid, axis_names=(DATA_AXIS, MODEL_AXIS))


def dp_sharded(model: CompiledModel, mesh: Mesh) -> ShardedModel:
    """Batch-data-parallel scoring over the mesh (replicated params).

    The inner jitted fn is re-wrapped with NamedShardings; XLA SPMD-
    partitions the traced graph — the einsum/matmul lowerings are untouched.
    """
    batch_spec = NamedSharding(mesh, P(DATA_AXIS))
    repl = NamedSharding(mesh, P())

    def _replicate(x):
        # make_array_from_callback works when the mesh spans processes
        # (device_put cannot target non-addressable devices); every host
        # holds the full params, so any index slice is servable locally
        arr = np.asarray(x)
        return jax.make_array_from_callback(
            arr.shape, repl, lambda idx: arr[idx]
        )

    params_sharded = jax.tree_util.tree_map(_replicate, model.params)
    inner = model._jit_fn  # the jitted full_fn(params, X, M)
    fn = getattr(inner, "__wrapped__", inner)
    jit_fn = jax.jit(
        fn,
        in_shardings=(
            jax.tree_util.tree_map(lambda _: repl, model.params),
            batch_spec,
            batch_spec,
        ),
        out_shardings=batch_spec,
    )
    return ShardedModel(
        base=model, mesh=mesh, _jit_fn=jit_fn, _params_sharded=params_sharded
    )


def mesh_sharded(
    model: CompiledModel,
    mesh: Mesh,
    wide_threshold: Optional[int] = None,
) -> ShardedModel:
    """DP over the batch axis + 1-D feature TP over wide param tensors
    (BASELINE config 5: the stacked model's 10k-dim linear stage).

    The compiled graph is re-jitted with *sharding constraints*, the
    GSPMD recipe (scaling-book): the batch rides ``P(data)``; any param
    leaf whose leading dimension is ≥ ``wide_threshold`` (and divisible
    by the model-axis size) gets ``P(model, …)`` on that dimension —
    a wide RegressionTable's ``num_coefs``/``cat_codes``/``cat_coefs``
    vectors, a wide first-layer NN weight. XLA then partitions the
    contracting dot exactly like the hand-written :func:`tp_linear`
    (local partial matmul + one psum over the ``model`` axis on ICI) —
    same collectives, derived by the partitioner instead of spelled out
    per model family, so EVERY lowering that consumes the wide leaf
    (chain stages included) shards without bespoke code.

    Narrow params replicate; a pure-DP mesh (model axis 1) degrades to
    exactly :func:`dp_sharded`.
    """
    if wide_threshold is None:
        from flink_jpmml_tpu.utils.config import CompileConfig

        wide_threshold = CompileConfig().tp_wide_threshold
    n_model = mesh.shape[MODEL_AXIS]
    batch_spec = NamedSharding(mesh, P(DATA_AXIS))
    repl = NamedSharding(mesh, P())

    flat, treedef = jax.tree_util.tree_flatten_with_path(model.params)
    specs = {}
    tp_leaves = []
    for path, leaf in flat:
        arr = np.asarray(leaf)
        wide = (
            n_model > 1
            and arr.ndim >= 1
            and arr.shape[0] >= wide_threshold
            and arr.shape[0] % n_model == 0
        )
        if wide:
            specs[path] = NamedSharding(
                mesh, P(MODEL_AXIS, *([None] * (arr.ndim - 1)))
            )
            tp_leaves.append(jax.tree_util.keystr(path))
        else:
            specs[path] = repl

    def _place(path, x):
        arr = np.asarray(x)
        s = specs[path]
        # make_array_from_callback serves local index slices even when
        # the mesh spans processes (cf. dp_sharded._replicate)
        return jax.make_array_from_callback(
            arr.shape, s, lambda idx: arr[idx]
        )

    params_sharded = jax.tree_util.tree_unflatten(
        treedef, [_place(p, leaf) for p, leaf in flat]
    )
    in_params_spec = jax.tree_util.tree_unflatten(
        treedef, [specs[p] for p, _ in flat]
    )
    inner = model._jit_fn
    fn = getattr(inner, "__wrapped__", inner)
    jit_fn = jax.jit(
        fn,
        in_shardings=(in_params_spec, batch_spec, batch_spec),
        out_shardings=batch_spec,
    )
    return ShardedModel(
        base=model,
        mesh=mesh,
        _jit_fn=jit_fn,
        _params_sharded=params_sharded,
        tp_sharded_leaves=tuple(tp_leaves),
    )


# ---------------------------------------------------------------------------
# 1-D tensor parallelism for wide linear models (config 5)
# ---------------------------------------------------------------------------


def tp_linear(
    mesh: Mesh,
    n_features: int,
    n_outputs: int,
):
    """→ fn(W [F,C] , b [C], X [B,F]) -> [B,C], feature dim sharded.

    ``shard_map`` over the mesh: X is sharded (data: batch, model: feature),
    W is sharded (model: feature rows); each device computes its partial
    ``x_shard @ w_shard`` and the partials are ``psum``-reduced over the
    ``model`` axis (ICI); the result is batch-sharded, feature-replicated —
    ready for the next (replicated) pipeline stage.
    """
    n_model = mesh.shape[MODEL_AXIS]
    if n_features % n_model != 0:
        raise InputValidationException(
            f"feature dim {n_features} must divide by model-axis size "
            f"{n_model} (pad the feature space)"
        )

    def _partial_matmul(W, b, X):
        part = jnp.dot(X, W, precision=HIGHEST)
        full = jax.lax.psum(part, MODEL_AXIS)
        return full + b

    fn = jax.shard_map(
        _partial_matmul,
        mesh=mesh,
        in_specs=(
            P(MODEL_AXIS, None),  # W: feature rows sharded
            P(),  # b: replicated
            P(DATA_AXIS, MODEL_AXIS),  # X: batch × feature sharded
        ),
        out_specs=P(DATA_AXIS, None),
    )
    return fn


@dataclass
class TpLinearScorer:
    """A feature-sharded logistic/linear scorer for very wide models
    (BASELINE config 5's 10k-dim sparse LR): ``sigmoid(X @ W + b)`` with W's
    feature dimension split over the ``model`` axis."""

    mesh: Mesh
    W: np.ndarray  # [F, C]
    b: np.ndarray  # [C]
    link: str = "logit"  # logit | identity | softmax

    def __post_init__(self):
        from flink_jpmml_tpu.compile.regression import softmax

        F, C = self.W.shape
        matmul = tp_linear(self.mesh, F, C)
        link = self.link

        def fn(W, b, X):
            y = matmul(W, b, X)
            if link == "logit":
                return 1.0 / (1.0 + jnp.exp(-y))
            if link == "softmax":
                return softmax(y)
            return y

        self._jit_fn = jax.jit(fn)
        wspec = NamedSharding(self.mesh, P(MODEL_AXIS, None))
        self._W = jax.device_put(self.W, wspec)
        self._b = jax.device_put(self.b, NamedSharding(self.mesh, P()))

    def predict(self, X) -> jnp.ndarray:
        n_data = self.mesh.shape[DATA_AXIS]
        if X.ndim != 2 or X.shape[1] != self.W.shape[0]:
            raise InputValidationException(
                f"input shape {getattr(X, 'shape', None)} != "
                f"[batch, {self.W.shape[0]}]"
            )
        if X.shape[0] % n_data != 0:
            raise InputValidationException(
                f"sharded batch {X.shape[0]} must divide by the data-axis "
                f"size {n_data} (pad the micro-batch)"
            )
        return self._jit_fn(self._W, self._b, X)


def mp_gp(mesh: Mesh, model) -> "callable":
    """Model-parallel GP inference: training instances sharded over the
    ``model`` axis.

    GP scoring is ``μ(x) = k(x, X_train)ᵀ α`` — a [B, N] kernel block
    against N stored instances. For large training sets N dominates
    memory and FLOPs, so each device holds an instance shard (its slice
    of the pre-scaled rows and of α), computes its partial
    ``k(x, X_shard) @ α_shard``, and a single ``psum`` over the model
    axis (ICI) combines the partials; the batch stays sharded over the
    ``data`` axis throughout. Squared-exponential kernels only (their
    ‖x−z‖² matmul expansion is what shards cleanly); others raise.

    ``model`` is a :class:`~flink_jpmml_tpu.pmml.ir.GaussianProcessIR`.
    → fn(X f32[B, D]) -> f32[B] with B divisible by the data axis.
    """
    from flink_jpmml_tpu.compile.gp import gp_prescale
    from flink_jpmml_tpu.pmml import ir
    from flink_jpmml_tpu.utils.exceptions import ModelCompilationException

    if not isinstance(model, ir.GaussianProcessIR):
        raise ModelCompilationException("mp_gp takes a GaussianProcessIR")
    if model.function_name != "regression":
        raise ModelCompilationException(
            "GaussianProcessModel supports functionName=regression only"
        )
    if model.kernel.kind not in ("radialBasis", "ARDSquaredExponential"):
        raise ModelCompilationException(
            "mp_gp supports the squared-exponential kernels "
            "(radialBasis, ARDSquaredExponential)"
        )
    alpha, lam, Zs, Zs_sq, _ = gp_prescale(model)
    N, D = Zs.shape
    inv_lam = (1.0 / lam).astype(np.float32)
    gamma = float(model.kernel.gamma)

    n_model = mesh.shape[MODEL_AXIS]
    pad = (-N) % n_model
    if pad:
        # zero-α padding rows contribute exactly 0 to the psum
        Zs = np.concatenate([Zs, np.zeros((pad, D), np.float32)])
        Zs_sq = np.concatenate([Zs_sq, np.zeros((pad,), np.float32)])
        alpha = np.concatenate([alpha, np.zeros((pad,))])
    alpha32 = alpha.astype(np.float32)

    def _partial(alpha_s, Zs_s, Zssq_s, il, X):
        xs = X * il[None, :]
        cross = jnp.dot(xs, Zs_s.T, precision=HIGHEST)  # [B, N/m]
        d2 = jnp.maximum(
            jnp.sum(xs**2, axis=1, keepdims=True)
            + Zssq_s[None, :]
            - 2.0 * cross,
            0.0,
        )
        part = jnp.dot(
            gamma * jnp.exp(-0.5 * d2), alpha_s, precision=HIGHEST
        )
        return jax.lax.psum(part, MODEL_AXIS)

    smapped = jax.shard_map(
        _partial,
        mesh=mesh,
        in_specs=(
            P(MODEL_AXIS),  # α: instance shards
            P(MODEL_AXIS, None),  # pre-scaled instances
            P(MODEL_AXIS),
            P(),  # inverse length-scales: replicated
            P(DATA_AXIS, None),  # X: batch sharded
        ),
        out_specs=P(DATA_AXIS),
    )
    jitted = jax.jit(smapped)

    n_data = mesh.shape[DATA_AXIS]
    # commit the constant params to their device shards ONCE — per-call
    # numpy args would re-transfer the whole training matrix every batch
    # (TpLinearScorer.__post_init__ sets the same pattern)
    alpha_d = jax.device_put(
        alpha32, NamedSharding(mesh, P(MODEL_AXIS))
    )
    Zs_d = jax.device_put(Zs, NamedSharding(mesh, P(MODEL_AXIS, None)))
    Zssq_d = jax.device_put(Zs_sq, NamedSharding(mesh, P(MODEL_AXIS)))
    il_d = jax.device_put(inv_lam, NamedSharding(mesh, P()))

    def predict(X):
        if X.shape[0] % n_data != 0:
            raise InputValidationException(
                f"batch {X.shape[0]} must divide by data-axis size "
                f"{n_data} (pad the micro-batch)"
            )
        if X.shape[1] != D:
            raise InputValidationException(
                f"feature dim {X.shape[1]} != model inputs {D}"
            )
        return jitted(alpha_d, Zs_d, Zssq_d, il_d, X)

    return predict
