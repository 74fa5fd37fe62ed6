"""Seeded LightGBM forest: trees grown best-first (leaf-wise) to exactly
``num_leaves`` leaves with no depth limit, as LightGBM grows the forest
of its own Higgs comparison (``docs/Experiments.rst``: 500 iterations,
``num_leaves`` 255, 255 histogram bins, learning rate 0.1), and its PMML
as JPMML-LightGBM writes a binary classifier: a ``modelChain`` whose
first segment sums the trees into the field ``lgbmValue`` and whose
second is a classification ``RegressionModel`` with
``normalizationMethod="logit"`` over that field.

The arrays are LightGBM's own model form (``split_feature``,
``threshold``, ``left_child``/``right_child`` with ``~leaf`` for a leaf,
``leaf_value``), so the plain reference (``reference/lgbm_ref.py``)
walks the very numbers the PMML holds and never sees the program.

How a tree grows: a seeded sample of rows like the stream's (N(0, 1.5),
28 features) stands for the training set. Each leaf gets one candidate
split: a feature drawn from the seed and the cut nearest a seeded
quantile (0.2 to 0.8) of that feature over the leaf's rows, and a gain
of its rows' count times an exponential draw, so that, as in a real
booster, heavy leaves are split first and some are split deep. The leaf
of largest gain is split until the tree has ``num_leaves`` leaves.
Thresholds lie on the 254 cuts of 255 equal-mass bins of N(0, 1.5)
(every feature alike; the wire stays uint8). Leaf values are the
learning rate times a seeded N(0, 1) draw; the first tree's leaves also
carry the init score, log(p / (1 - p)) of Higgs' signal share, as
LightGBM's ``boost_from_average`` writes it. Higgs has no missing
values, nor does the stream: ``defaultChild`` is the left child, as
LightGBM's ``default_left``.
"""

from __future__ import annotations

import heapq
import math
import os
import statistics
from dataclasses import dataclass

import numpy as np

# Higgs: 5,829,123 signal events of 11,000,000 (UCI "HIGGS")
SIGNAL_SHARE = 5829123 / 11000000
FEATURE_SD = 1.5  # the stream's features (lib/stream.py)
_SAMPLE_ROWS = 4096
_HEADER = (
    "<?xml version='1.0' encoding='utf-8'?>\n"
    '<PMML xmlns="http://www.dmg.org/PMML-4_3" version="4.3">\n'
    '<Header description="flink_jpmml_tpu benchmark model">'
    '<Application name="benchmark.lib.lgbm" /></Header>\n'
)


@dataclass(frozen=True)
class LgbmArrays:
    """Tree ``t``'s inner node ``i`` sends a record with
    ``x[feat[t, i]] <= thr[t, i]`` to ``left[t, i]``, else to
    ``right[t, i]``; a child ``c < 0`` is the leaf ``~c``. Node 0 is the
    root."""

    feat: np.ndarray   # int32   [T, leaves - 1]
    thr: np.ndarray    # float32 [T, leaves - 1]
    left: np.ndarray   # int32   [T, leaves - 1]
    right: np.ndarray  # int32   [T, leaves - 1]
    leaf: np.ndarray   # float64 [T, leaves]
    n_features: int

    @property
    def fields(self):
        return tuple(f"f{i}" for i in range(self.n_features))

    def depths(self) -> np.ndarray:
        """int [T]: each tree's largest leaf depth (splits on the path)."""
        T, inner = self.feat.shape
        depth = np.zeros((T, inner), np.int64)
        out = np.zeros(T, np.int64)
        for i in range(inner):  # a parent comes before its children
            for child in (self.left[:, i], self.right[:, i]):
                d = depth[:, i] + 1
                rows = np.flatnonzero(child >= 0)
                depth[rows, child[rows]] = d[rows]
                out = np.maximum(out, np.where(child < 0, d, 0))
        return out


def bin_cuts(bins: int) -> np.ndarray:
    """float32 [bins - 1]: the bounds of ``bins`` equal-mass bins of
    N(0, FEATURE_SD)."""
    nd = statistics.NormalDist(0.0, FEATURE_SD)
    return np.array([nd.inv_cdf(i / bins) for i in range(1, bins)],
                    np.float32)


def gen_arrays(seed: int, n_trees: int, num_leaves: int, n_features: int,
               bins: int, learning_rate: float) -> LgbmArrays:
    rng = np.random.default_rng([int(seed), 0x1C6B])
    cuts = bin_cuts(bins)
    X = rng.normal(0.0, FEATURE_SD, size=(_SAMPLE_ROWS, n_features)).astype(
        np.float32)
    inner = num_leaves - 1
    feat = np.zeros((n_trees, inner), np.int32)
    thr = np.zeros((n_trees, inner), np.float32)
    left = np.zeros((n_trees, inner), np.int32)
    right = np.zeros((n_trees, inner), np.int32)

    # one candidate a leaf, two leaves a split: the draws of a tree at once
    n_cand = 2 * inner + 1
    draws = iter(zip(
        rng.integers(n_features, size=n_trees * n_cand).tolist(),
        rng.uniform(0.2, 0.8, size=n_trees * n_cand).tolist(),
        rng.exponential(1.0, size=n_trees * n_cand).tolist(),
        rng.integers(cuts.size, size=n_trees * n_cand).tolist(),
    ))
    XT = np.ascontiguousarray(X.T)

    def candidate(rows):
        f, q, e, anywhere = next(draws)
        if not rows.size:
            return 0.0, f, anywhere
        vals = XT[f, rows]
        k = int(q * (rows.size - 1))
        b = min(int(np.searchsorted(cuts, np.partition(vals, k)[k])),
                cuts.size - 1)
        return -rows.size * e, f, b

    for t in range(n_trees):
        # heap of (-gain, order, leaf, rows, f, bin); a leaf is a slot in
        # the tree's leaf list, its parent's child pointer set on a split
        where = {0: None}  # leaf → (parent node, side)
        rows0 = np.arange(_SAMPLE_ROWS)
        g, f, b = candidate(rows0)
        heap = [(g, 0, 0, rows0, f, b)]
        n_leaves, order = 1, 1
        for node in range(inner):
            _, _, lf, rows, f, b = heapq.heappop(heap)
            feat[t, node], thr[t, node] = f, cuts[b]
            parent = where.pop(lf)
            if parent is not None:
                (left if parent[1] == 0 else right)[t, parent[0]] = node
            go_left = XT[f, rows] <= cuts[b]
            # the split leaf keeps its number on the left, the right is new
            for side, leaf_id, sub in ((0, lf, rows[go_left]),
                                       (1, n_leaves, rows[~go_left])):
                (left if side == 0 else right)[t, node] = ~leaf_id
                where[leaf_id] = (node, side)
                g, cf, cb = candidate(sub)
                heapq.heappush(heap, (g, order, leaf_id, sub, cf, cb))
                order += 1
            n_leaves += 1
    leaf = learning_rate * rng.normal(0.0, 1.0, size=(n_trees, num_leaves))
    leaf[0] += math.log(SIGNAL_SHARE / (1.0 - SIGNAL_SHARE))
    return LgbmArrays(feat, thr, left, right, leaf, n_features)


def _f(x) -> str:
    return repr(float(x))


def write_pmml(g: LgbmArrays, out_dir: str, name: str = "lgbm.pmml") -> str:
    """The arrays as JPMML-LightGBM writes a binary classifier (PMML
    4.3): split predicates ``lessOrEqual`` / ``greaterThan``,
    ``defaultChild`` the left child, the chain's field ``lgbmValue``,
    and the logit table of category "1" over it first, the empty table
    of "0" second."""
    fields = g.fields
    active = "".join(f'<MiningField name="{f}" />' for f in fields)
    schema = f"<MiningSchema>{active}</MiningSchema>"

    def node(t: int, c: int, pred: str) -> str:
        if c < 0:
            return f'<Node id="l{~c}" score="{_f(g.leaf[t, ~c])}">{pred}</Node>'
        f, v = int(g.feat[t, c]), _f(g.thr[t, c])
        return (
            f'<Node id="n{c}" defaultChild="{_child_id(g.left[t, c])}">{pred}'
            + node(t, int(g.left[t, c]),
                   f'<SimplePredicate field="f{f}" operator="lessOrEqual" '
                   f'value="{v}" />')
            + node(t, int(g.right[t, c]),
                   f'<SimplePredicate field="f{f}" operator="greaterThan" '
                   f'value="{v}" />')
            + "</Node>"
        )

    parts = [_HEADER, "<DataDictionary>"]
    parts += [
        f'<DataField name="{f}" optype="continuous" dataType="double" />'
        for f in fields
    ]
    parts.append(
        '<DataField name="_target" optype="categorical" dataType="integer">'
        '<Value value="0" /><Value value="1" /></DataField>'
        "</DataDictionary>\n"
    )
    parts.append(
        '<MiningModel functionName="classification">'
        f'<MiningSchema><MiningField name="_target" usageType="target" />'
        f"{active}</MiningSchema>"
        '<Segmentation multipleModelMethod="modelChain">\n'
        '<Segment id="1"><True /><MiningModel functionName="regression">'
        f"{schema}<Output>"
        '<OutputField name="lgbmValue" optype="continuous" dataType="double" '
        'feature="predictedValue" isFinalResult="false" /></Output>'
        '<Segmentation multipleModelMethod="sum">\n'
    )
    for t in range(g.feat.shape[0]):
        parts.append(
            f'<Segment id="{t + 1}"><True /><TreeModel '
            'functionName="regression" missingValueStrategy="defaultChild" '
            f'splitCharacteristic="binarySplit">{schema}'
            + node(t, 0, "<True />")
            + "</TreeModel></Segment>\n"
        )
    parts.append(
        "</Segmentation></MiningModel></Segment>\n"
        '<Segment id="2"><True />'
        '<RegressionModel functionName="classification" '
        'normalizationMethod="logit"><MiningSchema>'
        '<MiningField name="_target" usageType="target" />'
        '<MiningField name="lgbmValue" /></MiningSchema>'
        '<RegressionTable intercept="0.0" targetCategory="1">'
        '<NumericPredictor name="lgbmValue" coefficient="1.0" />'
        "</RegressionTable>"
        '<RegressionTable intercept="0.0" targetCategory="0" />'
        "</RegressionModel></Segment>\n"
        "</Segmentation></MiningModel></PMML>\n"
    )
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, name)
    with open(path, "w", encoding="utf-8") as fh:
        fh.write("".join(parts))
    return path


def _child_id(c: int) -> str:
    return f"l{~c}" if c < 0 else f"n{c}"
