"""Seeded XGBoost multi-class forest, as XGBoost's own Covertype demo
trains it (``demo/gpu_acceleration/cover_type.py``: ``num_round`` 3000,
every other parameter its default: ``max_depth`` 6, ``eta`` 0.3, the
histogram method) and as JPMML-XGBoost writes a multi-class classifier
(``MiningModelUtil.createClassification``): a ``modelChain`` of one
``<True/>`` regression segment a class, each summing that class's trees
into one ``predictedValue`` field ``xgbValue(k)``, then a classification
``RegressionModel`` with ``normalizationMethod="softmax"`` whose table
of class k is ``base_score + 1.0·xgbValue(k)``.

XGBoost grows one tree a class a round: tree ``r·C + k`` is round r's
tree of class k. The arrays keep that order (``[rounds, classes, ...]``)
and the document groups each class's trees in its own segment, so the
plain reference (``reference/xgbmc_ref.py``) walks the very numbers the
PMML holds and never sees the program.

How a tree is drawn: complete to ``depth`` (XGBoost's depthwise growth
stops there at the most), heap order, node ``i``'s children ``2i + 1``
(``x < thr``, and a missing value: ``defaultChild``) and ``2i + 2``; its
feature drawn from the seed, uniformly over the 54 columns. Covertype's
first ``n_quantitative`` columns (Elevation ... Horizontal distance to
fire points) are split on the cuts of ``max_bin`` equal-mass bins of the
stream's N(0, 1.5) (254 cuts at 255 bins: the uint8 rank wire's most);
the 44 indicator columns (4 wilderness areas, 40 soil types) hold 0 or
1, and XGBoost has one cut for them: 0.5, between the two. Leaf values
are ``eta · N(0, 1) / sqrt(1 + r / 10)`` in round r: a booster's steps
shrink as its fit improves, and 3,000 rounds of steps that did not would
put every margin ±16 away and every probability at 0 or 1. Thresholds
and leaves are float32, as XGBoost keeps them, written as the shortest
decimal that reads back to the same float32.
"""

from __future__ import annotations

import os
from dataclasses import dataclass

import numpy as np

from . import lgbm

FEATURE_SD = lgbm.FEATURE_SD  # the stream's features (lib/stream.py)
INDICATOR_CUT = 0.5
# the round at which a leaf's scale has fallen to 1/sqrt(2)
SHRINK_ROUNDS = 10.0
_HEADER = (
    "<?xml version='1.0' encoding='utf-8'?>\n"
    '<PMML xmlns="http://www.dmg.org/PMML-4_3" version="4.3">\n'
    '<Header description="flink_jpmml_tpu benchmark model">'
    '<Application name="benchmark.lib.xgbmc" /></Header>\n'
)


@dataclass(frozen=True)
class XgbmcArrays:
    """Round ``r``'s tree of class ``k``: inner node ``i`` sends a record
    with ``x[feat[r, k, i]] < thr[r, k, i]`` to ``2i + 1``, else to
    ``2i + 2``; node ``2**depth - 1 + j`` is the leaf ``leaf[r, k, j]``."""

    feat: np.ndarray   # int32   [R, C, 2**depth - 1]
    thr: np.ndarray    # float32 [R, C, 2**depth - 1]
    leaf: np.ndarray   # float32 [R, C, 2**depth]
    base_score: float
    n_features: int

    @property
    def fields(self):
        return tuple(f"f{i}" for i in range(self.n_features))

    @property
    def classes(self):
        return tuple(str(k) for k in range(self.leaf.shape[1]))

    @property
    def depth(self) -> int:
        return int(self.leaf.shape[2]).bit_length() - 1


def gen_arrays(seed: int, n_rounds: int, n_classes: int, depth: int,
               n_features: int, n_quantitative: int, max_bin: int,
               eta: float, base_score: float) -> XgbmcArrays:
    rng = np.random.default_rng([int(seed), 0xC0F7])
    inner = 2 ** depth - 1
    shape = (n_rounds, n_classes, inner)
    feat = rng.integers(0, n_features, size=shape).astype(np.int32)
    cuts = lgbm.bin_cuts(max_bin)
    thr = np.where(
        feat < n_quantitative,
        cuts[rng.integers(0, cuts.size, size=shape)],
        np.float32(INDICATOR_CUT),
    ).astype(np.float32)
    scale = eta / np.sqrt(1.0 + np.arange(n_rounds) / SHRINK_ROUNDS)
    leaf = (rng.normal(0.0, 1.0, size=(n_rounds, n_classes, inner + 1))
            * scale[:, None, None]).astype(np.float32)
    return XgbmcArrays(feat, thr, leaf, float(base_score), n_features)


def _f32_strings(a: np.ndarray) -> np.ndarray:
    """The shortest decimal of each float32 that reads back to it."""
    return np.array([str(v) for v in np.asarray(a, np.float32).ravel()],
                    dtype=object).reshape(a.shape)


def write_pmml(g: XgbmcArrays, out_dir: str, name: str = "xgbmc.pmml") -> str:
    """The arrays as JPMML-XGBoost writes a multi-class classifier (PMML
    4.3): split predicates ``lessThan`` / ``greaterOrEqual``,
    ``defaultChild`` the left child, each tree's ``MiningSchema`` the
    fields it reads, a class's segment summing its trees into
    ``xgbValue(k)``, and the softmax table of each class over its own
    field with ``base_score`` as the intercept."""
    R, C, inner = g.feat.shape
    fields = g.fields
    thr_s, leaf_s = _f32_strings(g.thr), _f32_strings(g.leaf)
    active = "".join(f'<MiningField name="{f}" />' for f in fields)

    def tree(r: int, k: int) -> str:
        feat, ts, ls = g.feat[r, k], thr_s[r, k], leaf_s[r, k]
        parts = []

        def node(i: int, nid: str, pred: str) -> None:
            if i >= inner:
                parts.append(f'<Node id="{nid}" score="{ls[i - inner]}">'
                             f"{pred}</Node>")
                return
            f, v = int(feat[i]), ts[i]
            parts.append(f'<Node id="{nid}" defaultChild="{2 * i + 1}">{pred}')
            node(2 * i + 1, str(2 * i + 1),
                 f'<SimplePredicate field="f{f}" operator="lessThan" '
                 f'value="{v}" />')
            node(2 * i + 2, str(2 * i + 2),
                 f'<SimplePredicate field="f{f}" operator="greaterOrEqual" '
                 f'value="{v}" />')
            parts.append("</Node>")

        node(0, "0", "<True />")
        used = "".join(f'<MiningField name="f{f}" />'
                       for f in sorted(set(feat.tolist())))
        return (f'<Segment id="{r + 1}"><True /><TreeModel '
                'functionName="regression" missingValueStrategy="defaultChild" '
                f'splitCharacteristic="binarySplit"><MiningSchema>{used}'
                f'</MiningSchema>{"".join(parts)}</TreeModel></Segment>\n')

    out = [_HEADER, "<DataDictionary>"]
    out += [f'<DataField name="{f}" optype="continuous" dataType="float" />'
            for f in fields]
    out.append(
        '<DataField name="_target" optype="categorical" dataType="integer">'
        + "".join(f'<Value value="{c}" />' for c in g.classes)
        + "</DataField></DataDictionary>\n"
        '<MiningModel functionName="classification">'
        f'<MiningSchema><MiningField name="_target" usageType="target" />'
        f"{active}</MiningSchema>"
        '<Segmentation multipleModelMethod="modelChain">\n'
    )
    for k in range(C):
        out.append(
            f'<Segment id="{k + 1}"><True /><MiningModel '
            f'functionName="regression"><MiningSchema>{active}</MiningSchema>'
            f'<Output><OutputField name="xgbValue({k})" optype="continuous" '
            'dataType="float" feature="predictedValue" isFinalResult="false" />'
            '</Output><Segmentation multipleModelMethod="sum">\n'
        )
        out += [tree(r, k) for r in range(R)]
        out.append("</Segmentation></MiningModel></Segment>\n")
    base = repr(g.base_score)
    out.append(
        f'<Segment id="{C + 1}"><True />'
        '<RegressionModel functionName="classification" '
        'normalizationMethod="softmax"><MiningSchema>'
        '<MiningField name="_target" usageType="target" />'
        + "".join(f'<MiningField name="xgbValue({k})" />' for k in range(C))
        + "</MiningSchema>"
        + "".join(
            f'<RegressionTable intercept="{base}" targetCategory="{k}">'
            f'<NumericPredictor name="xgbValue({k})" coefficient="1.0" />'
            "</RegressionTable>" for k in range(C))
        + "</RegressionModel></Segment>\n"
        "</Segmentation></MiningModel></PMML>\n"
    )
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, name)
    with open(path, "w", encoding="utf-8") as fh:
        fh.write("".join(out))
    return path
