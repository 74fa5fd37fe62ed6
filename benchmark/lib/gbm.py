"""Seeded GBM generator: a copy, in array form, of the program's
``assets_gen.gen_gbm`` (flink_jpmml_tpu/assets_gen.py:162-217) and its
``_gen_tree_nodes`` (:123-152).

Same model family — a MiningModel ``sum`` of complete binary regression
TreeModels with complementary (lessThan t / greaterOrEqual t) children,
``defaultChild`` left, thresholds on a per-feature histogram grid,
leaf scores N(0, 0.1), ``Targets rescaleConstant`` as the base score —
but the trees are drawn as arrays first, so the plain reference
(benchmark/reference/gbm_ref.py) walks the very numbers the PMML holds
and never sees the program's parser. Grid values are rounded to float32
so that ``x < t`` means the same in float32 and float64.
"""

from __future__ import annotations

import os
from dataclasses import dataclass

import numpy as np

_HEADER = (
    "<?xml version='1.0' encoding='utf-8'?>\n"
    '<PMML xmlns="http://www.dmg.org/PMML-4_3" version="4.3">\n'
    '<Header description="flink_jpmml_tpu benchmark model">'
    '<Application name="benchmark.lib.gbm" /></Header>\n'
)


@dataclass(frozen=True)
class GbmArrays:
    """Heap-ordered complete trees: node ``i`` has children ``2i+1``
    (x < thr, and missing) and ``2i+2``; the last level indexes
    ``leaf``."""

    feat: np.ndarray   # int32  [T, 2**depth - 1]
    thr: np.ndarray    # float32 [T, 2**depth - 1]
    leaf: np.ndarray   # float64 [T, 2**depth]
    base_score: float
    depth: int
    n_features: int

    @property
    def fields(self):
        return tuple(f"f{i}" for i in range(self.n_features))


def gen_arrays(seed: int, n_trees: int, depth: int, n_features: int,
               hist_bins: int, base_score: float = 0.5,
               value_scale: float = 0.1) -> GbmArrays:
    rng = np.random.default_rng([int(seed), 0x6B6D])
    grids = np.sort(
        rng.normal(0.0, 1.0, size=(n_features, hist_bins)), axis=1
    ).astype(np.float32)
    inner = 2 ** depth - 1
    feat = rng.integers(0, n_features, size=(n_trees, inner)).astype(np.int32)
    bins = rng.integers(0, hist_bins, size=(n_trees, inner))
    thr = grids[feat, bins]
    leaf = rng.normal(0.0, value_scale, size=(n_trees, 2 ** depth))
    return GbmArrays(feat, thr, leaf, float(base_score), depth, n_features)


def _f(x) -> str:
    return repr(float(x))


def write_pmml(g: GbmArrays, out_dir: str, name: str = "gbm.pmml") -> str:
    """The arrays as a PMML 4.3 document, element for element what
    ``assets_gen.gen_gbm`` writes."""
    fields = g.fields
    schema = "<MiningSchema>" + "".join(
        f'<MiningField name="{f}" usageType="active" />' for f in fields
    ) + "</MiningSchema>"
    n_inner = g.feat.shape[1]

    def node(t: int, i: int, nid: str, pred: str) -> str:
        if i >= n_inner:
            return (f'<Node id="{nid}" score="{_f(g.leaf[t, i - n_inner])}">'
                    f"{pred}</Node>")
        f, v = int(g.feat[t, i]), _f(g.thr[t, i])
        lid, rid = f"{i}l", f"{i}r"
        return (
            f'<Node id="{nid}" defaultChild="{lid}">{pred}'
            + node(t, 2 * i + 1, lid,
                   f'<SimplePredicate field="f{f}" operator="lessThan" '
                   f'value="{v}" />')
            + node(t, 2 * i + 2, rid,
                   f'<SimplePredicate field="f{f}" operator="greaterOrEqual" '
                   f'value="{v}" />')
            + "</Node>"
        )

    parts = [_HEADER, "<DataDictionary>"]
    parts += [
        f'<DataField name="{f}" optype="continuous" dataType="double" />'
        for f in fields
    ]
    parts.append("</DataDictionary>\n")
    parts.append(
        f'<MiningModel modelName="gbm-{g.feat.shape[0]}" '
        f'functionName="regression">{schema}'
        f'<Targets><Target rescaleConstant="{_f(g.base_score)}" /></Targets>'
        '<Segmentation multipleModelMethod="sum">\n'
    )
    for t in range(g.feat.shape[0]):
        parts.append(
            f'<Segment id="{t}"><True /><TreeModel functionName="regression" '
            'missingValueStrategy="defaultChild" '
            f'splitCharacteristic="binarySplit">{schema}'
            + node(t, 0, "r", "<True />")
            + "</TreeModel></Segment>\n"
        )
    parts.append("</Segmentation></MiningModel></PMML>\n")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, name)
    with open(path, "w", encoding="utf-8") as fh:
        fh.write("".join(parts))
    return path
