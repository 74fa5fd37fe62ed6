"""Top-level PMML → JAX compiler: dispatch, jit, decode.

Replaces the reference's ``PmmlModel.fromReader`` + ``predict`` core
(SURVEY.md §3 row B1: expected upstream ``…/api/PmmlModel.scala``
[UNVERIFIED]) with an ahead-of-time compile: parse → lower → ``jax.jit``
with a fixed batch shape. The per-record ``predict(vector, replaceNan)``
becomes ``CompiledModel.predict(X, M)`` over a micro-batch; totality
(capability C5) is the ``valid`` lane, decoded to ``Prediction`` objects by
:meth:`CompiledModel.decode`.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from flink_jpmml_tpu.compile import prepare
from flink_jpmml_tpu.compile.clustering import lower_clustering
from flink_jpmml_tpu.compile.common import (
    Lowered,
    LowerCtx,
    ModelOutput,
    apply_targets,
    build_codecs,
    extract_invalid_policy,
    extract_missing_replacements,
)
from flink_jpmml_tpu.compile.bayes import lower_naive_bayes
from flink_jpmml_tpu.compile.exprs import lower_expression
from flink_jpmml_tpu.compile.glm import lower_general_regression
from flink_jpmml_tpu.compile.knn import lower_knn
from flink_jpmml_tpu.compile.mining import lower_mining
from flink_jpmml_tpu.compile.neural import lower_neural_network
from flink_jpmml_tpu.compile.regression import lower_regression
from flink_jpmml_tpu.compile.ruleset import lower_ruleset
from flink_jpmml_tpu.compile.scorecard import lower_scorecard
from flink_jpmml_tpu.compile.svm import lower_svm
from flink_jpmml_tpu.compile.trees import lower_tree
from flink_jpmml_tpu.models.prediction import Prediction, decode_batch
from flink_jpmml_tpu.pmml import ir
from flink_jpmml_tpu.pmml.outputs import (
    compute_outputs,
    validate_output_fields,
)
from flink_jpmml_tpu.utils.config import CompileConfig
from flink_jpmml_tpu.utils.exceptions import ModelCompilationException

_UNSET = object()  # sentinel: quantized fast path not yet attempted


def lower_model(model: ir.ModelIR, ctx: LowerCtx) -> Lowered:
    """Dispatch a parsed model to its family lowerer."""
    if isinstance(model, ir.TreeModelIR):
        return lower_tree(model, ctx)
    if isinstance(model, ir.RegressionModelIR):
        return lower_regression(model, ctx)
    if isinstance(model, ir.NeuralNetworkIR):
        return lower_neural_network(model, ctx)
    if isinstance(model, ir.ClusteringModelIR):
        return lower_clustering(model, ctx)
    if isinstance(model, ir.ScorecardIR):
        return lower_scorecard(model, ctx)
    if isinstance(model, ir.RuleSetIR):
        return lower_ruleset(model, ctx)
    if isinstance(model, ir.GeneralRegressionIR):
        return lower_general_regression(model, ctx)
    if isinstance(model, ir.NaiveBayesIR):
        return lower_naive_bayes(model, ctx)
    if isinstance(model, ir.SvmModelIR):
        return lower_svm(model, ctx)
    if isinstance(model, ir.NearestNeighborIR):
        return lower_knn(model, ctx)
    if isinstance(model, ir.AnomalyDetectionIR):
        from flink_jpmml_tpu.compile.anomaly import lower_anomaly

        return lower_anomaly(model, ctx)
    if isinstance(model, ir.GaussianProcessIR):
        from flink_jpmml_tpu.compile.gp import lower_gp

        return lower_gp(model, ctx)
    if isinstance(model, ir.BaselineIR):
        from flink_jpmml_tpu.compile.baseline import lower_baseline

        return lower_baseline(model, ctx)
    if isinstance(model, ir.AssociationIR):
        from flink_jpmml_tpu.compile.assoc import lower_association

        return lower_association(model, ctx)
    if isinstance(model, ir.TimeSeriesIR):
        from flink_jpmml_tpu.compile.timeseries import lower_time_series

        return lower_time_series(model, ctx)
    if isinstance(model, ir.BayesianNetworkIR):
        from flink_jpmml_tpu.compile.bayesnet import lower_bayesian_network

        return lower_bayesian_network(model, ctx)
    if isinstance(model, ir.TextModelIR):
        from flink_jpmml_tpu.compile.textmodel import lower_text_model

        return lower_text_model(model, ctx)
    if isinstance(model, ir.MiningModelIR):
        return lower_mining(model, ctx)
    raise ModelCompilationException(
        f"unsupported model IR {type(model).__name__}"
    )


@dataclass
class CompiledModel:
    """A PMML document compiled to a jitted batch scorer.

    ``predict`` is the hot path: numpy/JAX arrays in, :class:`ModelOutput`
    out, no host-side per-record work. ``score_records`` / ``score_dense``
    are convenience wrappers that also decode to ``Prediction`` lists.
    """

    field_space: prepare.FieldSpace
    labels: Tuple[str, ...]
    params: Dict
    batch_size: Optional[int]
    _jit_fn: object
    model_name: Optional[str] = None
    _doc: Optional[ir.PmmlDocument] = None
    _config: Optional[CompileConfig] = None
    _quantized: object = _UNSET
    output_fields: Tuple[ir.OutputField, ...] = ()  # top-level <Output>
    # scorecard reason codes: (ReasonCodeMeta, n_characteristics) when the
    # document declares useReasonCodes and the metadata is complete
    _reason: Optional[tuple] = None
    # association: per-rule metadata (ruleFeature-keyed dicts, document
    # order) + the static confidence/support ranking, feeding
    # <Output feature="ruleValue"> fields at decode
    _rule_meta: Optional[Tuple[dict, ...]] = None
    _rule_order: Optional[Tuple[int, ...]] = None
    # embedded <ModelVerification> vectors + the target name they may
    # reference (verify() replays them; ModelReader gates loads on it)
    _verification: Optional[ir.ModelVerification] = None
    _target_field: Optional[str] = None
    # selectAll: segment ids, decoding probs = [values ∥ active] into
    # the per-segment outputs mapping
    _segment_ids: Optional[Tuple[str, ...]] = None
    # clustering: its probabilities mapping holds per-entity comparison
    # scores — the entityId/affinity output features read it; the order
    # ("asc" distances / "desc" similarities) ranks entities for rank-k
    # entityId
    _entity_scores: bool = False
    _entity_order: Optional[str] = None
    # KNN instanceIdVariable: (instance ids, k, n_label_columns) — the
    # last k probs columns are ranked neighbor indices
    _neighbor_meta: Optional[tuple] = None

    @property
    def is_classification(self) -> bool:
        return bool(self.labels)

    @property
    def active_fields(self) -> Tuple[str, ...]:
        return self.field_space.fields

    def predict(self, X, M) -> ModelOutput:
        return self._jit_fn(self.params, X, M)

    def quantized_scorer(self):
        """Rank-wire fast path (qtrees.py) for this model, or None.

        Built lazily on first call and cached; eligible only for regression
        tree ensembles whose splits are all numeric comparisons. The wire
        ships each record as per-feature threshold ranks (uint8/uint16) —
        bit-exact with this model's f32 scoring — cutting host→device bytes
        ~4x for the north-star GBM stream.
        """
        if self._quantized is _UNSET:
            from flink_jpmml_tpu.compile.qtrees import build_quantized_scorer

            # documents outside the fast path's contract return None
            # without raising; a probe that raises is a defect on every
            # backend and propagates
            self._quantized = (
                build_quantized_scorer(
                    self._doc,
                    batch_size=self.batch_size,
                    config=self._config,
                )
                if self._doc is not None
                else None
            )
            # the parse tree is only needed for this probe — release it so a
            # long-lived served model doesn't pin the whole IR
            self._doc = None
            self._config = None
        return self._quantized

    @property
    def has_verification(self) -> bool:
        return self._verification is not None

    def verify(self) -> List[str]:
        """Replay the document's embedded ModelVerification records.

        → mismatch descriptions; empty = verified (or nothing embedded).
        The JPMML ``Evaluator.verify()`` contract (SURVEY.md §1 C1/C2):
        callers that require a verified model raise
        ModelVerificationException on a non-empty result (ModelReader
        does, by default, when the document embeds vectors).
        """
        from flink_jpmml_tpu.compile.verify import run_verification

        return run_verification(self, self._target_field)

    def warmup(self) -> "CompiledModel":
        """Force compilation (and params transfer) ahead of the hot path."""
        b = self.batch_size or 1
        X = np.zeros((b, self.field_space.arity), np.float32)
        M = np.zeros((b, self.field_space.arity), bool)
        jax.block_until_ready(self.predict(X, M))
        return self

    # -- convenience wrappers (host-side decode; not for the hot loop) -----

    def score_dense(
        self, vectors, replace_nan: Optional[float] = None
    ) -> List[Prediction]:
        X, M = prepare.from_dense(self.field_space, vectors, replace_nan)
        return self._score(X, M, n=X.shape[0])

    def score_records(self, records: Sequence[dict]) -> List[Prediction]:
        X, M = prepare.from_records(self.field_space, records)
        return self._score(X, M, n=X.shape[0])

    def _score(self, X, M, n: int) -> List[Prediction]:
        if self.batch_size is not None:
            X, M, _ = prepare.pad_batch(X, M, self.batch_size)
        out = self.predict(X, M)
        return self.decode(out, n)

    def decode(self, out: ModelOutput, n: Optional[int] = None) -> List[Prediction]:
        value = np.asarray(out.value)[:n]
        valid = np.asarray(out.valid)[:n]
        labels = None
        probabilities = None
        if self.is_classification and out.label_idx is not None:
            idx = np.asarray(out.label_idx)[:n]
            labels = [self.labels[i] for i in idx]
            # association: probs is the fired-rule mask, not class
            # probabilities — consumed below for ruleValue ranking.
            # KNN-with-ids: only the first L columns are vote shares
            # (the rest are ranked neighbor indices)
            if out.probs is not None and self._rule_meta is None:
                P = np.asarray(out.probs)[:n]
                probabilities = [
                    dict(zip(self.labels, row.tolist())) for row in P
                ]
        preds = decode_batch(
            value.tolist(), valid.tolist(), labels, probabilities
        )
        if self._rule_meta is not None and not self.output_fields:
            # oracle parity: with no <Output> declared, the association
            # winner's metadata is still surfaced (interp.py does the same)
            idx = np.asarray(out.label_idx)[:n]
            preds = [
                p if p.is_empty
                else dataclasses.replace(p, outputs=self._rule_meta[idx[i]])
                for i, p in enumerate(preds)
            ]
        if self._segment_ids is not None and not self.output_fields:
            # selectAll: probs = [values ∥ active mask]; surface every
            # active segment's value (None where inactive), oracle parity
            S = len(self._segment_ids)
            P = np.asarray(out.probs)[:n]
            preds = [
                p if p.is_empty
                else dataclasses.replace(p, outputs={"segments": {
                    sid: (float(P[i, j]) if P[i, S + j] > 0.5 else None)
                    for j, sid in enumerate(self._segment_ids)
                }})
                for i, p in enumerate(preds)
            ]
        if self.output_fields:
            # top-level <Output> post-processing (pmml/outputs.py): only
            # documents that declare it pay this host-side per-record step
            rc_rows = None
            if self._reason is not None and any(
                of.feature == "reasonCode" for of in self.output_fields
            ):
                meta, C = self._reason
                P = np.asarray(out.probs)[:n]  # [B, 2C]: partials ∥ attr
                rc_rows = [
                    meta.rank(P[i, :C], P[i, C:].astype(np.int32))
                    for i in range(P.shape[0])
                ]
            rankings = self._entity_rankings(out, n)
            rank_rows = None
            if self._rule_meta is not None and out.probs is not None and any(
                of.feature == "ruleValue" for of in self.output_fields
            ):
                # fired mask (document order) → ranked fired-rule metadata
                # via the static confidence/support order
                fired = np.asarray(out.probs)[:n] > 0.5
                rank_rows = [
                    tuple(
                        self._rule_meta[j]
                        for j in self._rule_order
                        if fired[i, j]
                    )
                    for i in range(fired.shape[0])
                ]
            preds = [
                p
                if p.is_empty
                else dataclasses.replace(
                    p,
                    outputs=compute_outputs(
                        self.output_fields,
                        p.score.value,
                        p.target.label if p.target else None,
                        p.target.probabilities if p.target else None,
                        reason_codes=(
                            rc_rows[i] if rc_rows is not None else None
                        ),
                        rule_ranking=(
                            rank_rows[i] if rank_rows is not None else None
                        ),
                        entity_scores=(
                            (p.target.probabilities or None)
                            if self._entity_scores and p.target
                            else None
                        ),
                        entity_ranking=(
                            rankings[i] if rankings is not None else None
                        ),
                    ),
                )
                for i, p in enumerate(preds)
            ]
        return preds

    def _entity_rankings(self, out, n):
        """Per-record best-first entity ids for rank-k entityId decode:
        clustering sorts its score row; KNN-with-ids reads the ranked
        neighbor-index columns the kernel appended."""
        if not any(of.feature == "entityId" for of in self.output_fields):
            return None
        if self._neighbor_meta is not None and out.probs is not None:
            ids, k, L = self._neighbor_meta
            P = np.asarray(out.probs)[:n]
            idx = P[:, L:].astype(np.int64)  # ranked neighbor indices
            return [
                tuple(ids[j] for j in idx[i]) for i in range(idx.shape[0])
            ]
        if self._entity_order is not None and out.probs is not None:
            P = np.asarray(out.probs)[:n]
            sign = 1.0 if self._entity_order == "asc" else -1.0
            order = np.argsort(sign * P, axis=1, kind="stable")
            return [
                tuple(self.labels[j] for j in order[i])
                for i in range(order.shape[0])
            ]
        return None


def compile_pmml(
    doc: ir.PmmlDocument,
    batch_size: Optional[int] = None,
    config: Optional[CompileConfig] = None,
    donate: Optional[bool] = None,
    mesh=None,
):
    """Parse-tree → jitted scorer (capability C1 + the north-star hot path).

    ``batch_size`` fixes the traced batch shape (None = shape-polymorphic:
    jit re-traces per distinct batch size — fine for tests, wrong for the
    streaming runtime, which always pads to a fixed size).

    ``mesh`` (a ``jax.sharding.Mesh``, BASELINE config 5): returns a
    :class:`~flink_jpmml_tpu.parallel.sharding.ShardedModel` instead —
    batch sharded over ``data``, any param tensor at least
    ``config.tp_wide_threshold`` wide feature-sharded over ``model``
    (the stacked model's 10k-dim linear stage compiles to a local
    partial matmul + one psum over ICI; see ``mesh_sharded``).
    """
    config = config or CompileConfig()
    fields = doc.active_fields
    if not fields:
        raise ModelCompilationException("model has no active fields")
    codecs = build_codecs(doc.data_dictionary)

    # TransformationDictionary derived fields become extra input columns,
    # computed on-device from the raw columns before the model body runs
    # (declaration order; later fields may reference earlier ones). The
    # user-facing field space stays the raw active fields.
    derived = doc.transformations.derived_fields
    field_index = {f: i for i, f in enumerate(fields)}
    derived_fns = []
    for df in derived:
        dctx = LowerCtx(
            field_index=dict(field_index), codecs=codecs, config=config
        )
        derived_fns.append(lower_expression(df.expression, dctx))
        if df.name in field_index:
            raise ModelCompilationException(
                f"derived field {df.name!r} shadows an existing field"
            )
        field_index[df.name] = len(field_index)

    ctx = LowerCtx(
        field_index=field_index,
        codecs=codecs,
        config=config,
    )
    lowered = lower_model(doc.model, ctx)

    # top-level mining-schema missingValueReplacement (C4), vectorized —
    # sized to the RAW columns (it runs before derived columns exist,
    # mirroring the oracle's replacement → transformations order)
    raw_ctx = LowerCtx(
        field_index={f: i for i, f in enumerate(fields)},
        codecs=codecs,
        config=config,
    )
    repl, has_repl = extract_missing_replacements(
        doc.model.mining_schema, raw_ctx
    )
    any_repl = bool(has_repl.any())
    targets = doc.targets
    # DataDictionary validity × invalidValueTreatment (None = nothing can
    # be invalid; the sanitize stage compiles away entirely)
    ivp = extract_invalid_policy(
        doc.data_dictionary, doc.model.mining_schema, raw_ctx
    )

    def full_fn(params, X, M):
        X = X.astype(jnp.float32)
        lane_bad = None
        if ivp is not None:
            # a categorical cell is invalid unless it holds an exact code
            # in [0, n_declared): covers the +inf marker that
            # prepare.encode_cell emits for undeclared *strings* AND
            # out-of-table pre-encoded codes on the dense-vector path
            # (oracle-parity: both are returnInvalid by default)
            inv = (
                ivp["has_cat"][None, :]
                & ~M
                & (
                    (X < 0)
                    | (X >= ivp["cat_n"][None, :])
                    | (X != jnp.round(X))
                )
            )
            if ivp["has_ivl"] is not None:
                xk = X[:, :, None]
                ge = jnp.where(
                    ivp["lo_open"][None], xk > ivp["lo"][None],
                    xk >= ivp["lo"][None],
                )
                le = jnp.where(
                    ivp["hi_open"][None], xk < ivp["hi"][None],
                    xk <= ivp["hi"][None],
                )
                in_any = jnp.any(ge & le, axis=-1)
                inv = inv | (ivp["has_ivl"][None, :] & ~in_any & ~M)
            treat = ivp["treat"][None, :]
            X = jnp.where(inv & (treat == 3), ivp["repl"][None, :], X)
            M = M | (inv & (treat == 1))
            lane_bad = jnp.any(inv & (treat == 2), axis=1)
            # asIs / asMissing / returnInvalid categorical markers become
            # a never-match code: not missing, equal/isIn to nothing —
            # exactly "use the (undeclared) value as is"
            X = jnp.where(
                inv & ivp["has_cat"][None, :] & (treat != 3), -2.0, X
            )
            X = jnp.where(M, 0.0, X)
        if any_repl:
            use = M & has_repl[None, :]
            X = jnp.where(use, repl[None, :], X)
            M = M & ~has_repl[None, :]
        for dfn in derived_fns:  # appends columns in declaration order
            v, miss = dfn(X, M)
            X = jnp.concatenate(
                [X, v.astype(jnp.float32)[:, None]], axis=1
            )
            M = jnp.concatenate([M, miss[:, None]], axis=1)
        out = lowered.fn(params, X, M)
        out = apply_targets(out, targets)
        if lane_bad is not None:
            out = out._replace(valid=out.valid & ~lane_bad)
        return out

    donate_args = (
        config.donate_batches if donate is None else donate
    )
    jit_fn = jax.jit(
        full_fn, donate_argnums=(1, 2) if donate_args else ()
    )

    validate_output_fields(doc.output_fields)
    reason = None
    if isinstance(doc.model, ir.ScorecardIR) and doc.model.use_reason_codes:
        from flink_jpmml_tpu.compile.scorecard import ReasonCodeMeta

        wants_rc = any(
            of.feature == "reasonCode" for of in doc.output_fields
        )
        try:
            reason = (
                ReasonCodeMeta(doc.model),
                len(doc.model.characteristics),
            )
        except ModelCompilationException:
            if wants_rc:
                raise  # requested but the metadata is incomplete
            reason = None
    rule_meta = rule_order = None
    if isinstance(doc.model, ir.AssociationIR):
        from flink_jpmml_tpu.pmml.interp import rule_meta_dict

        rules = doc.model.rules
        rule_meta = tuple(rule_meta_dict(r) for r in rules)
        rule_order = tuple(sorted(
            range(len(rules)),
            key=lambda i: (-rules[i].confidence, -rules[i].support, i),
        ))
    segment_ids = None
    if (
        isinstance(doc.model, ir.MiningModelIR)
        and doc.model.segmentation.multiple_model_method == "selectAll"
    ):
        segment_ids = tuple(
            s.segment_id or str(i)
            for i, s in enumerate(doc.model.segmentation.segments)
        )
    name = getattr(doc.model, "model_name", None)
    entity_scores = isinstance(doc.model, ir.ClusteringModelIR)
    entity_order = None
    if entity_scores:
        entity_order = (
            "desc" if doc.model.measure.kind == "similarity" else "asc"
        )
    neighbor_meta = None
    if (
        isinstance(doc.model, ir.NearestNeighborIR)
        and doc.model.instance_ids
    ):
        neighbor_meta = (
            doc.model.instance_ids,
            doc.model.n_neighbors,
            len(lowered.labels),
        )
    compiled = CompiledModel(
        field_space=prepare.FieldSpace(fields=fields, codecs=ctx.codecs),
        labels=lowered.labels,
        params=jax.device_put(lowered.params),
        batch_size=batch_size,
        _jit_fn=jit_fn,
        model_name=name,
        _doc=doc,
        _config=config,
        output_fields=doc.output_fields,
        _reason=reason,
        _rule_meta=rule_meta,
        _rule_order=rule_order,
        _verification=doc.verification,
        _target_field=doc.target_field,
        _segment_ids=segment_ids,
        _entity_scores=entity_scores,
        _entity_order=entity_order,
        _neighbor_meta=neighbor_meta,
    )
    if mesh is not None:
        from flink_jpmml_tpu.parallel.sharding import mesh_sharded

        return mesh_sharded(
            compiled, mesh, wide_threshold=config.tp_wide_threshold
        )
    return compiled
