"""Path builder ``block_classes``: ``paths/block_twin.py`` for a
classifier whose rank-wire program returns its whole output, the triple
(the label's probability ``[B]``, every class's probability ``[B, C]``,
the label ``[B]``), and reads all three at the sink, as a deployment
that delivers the class probabilities does. The state folds the label's
probability (``compile/statekernel.py``), and so does the benchmark's
sink: ``on_batch`` gets it, one score a record. The warm-up stream's
probabilities and labels are kept (``warm_probs``, ``warm_labels``, by
offset) for a warm-up check that holds every class.

The one reach past the program's public entry points: the pipeline's
sink is swapped for this one after ``paths/block.py`` has built it
(``BlockPipeline._sink``), since that sink reads one score a record.

Loading this file asks the program first whether its rank wire takes a
softmax chain (``compile.qtrees._match_chain`` on a two-class chain of
one tree each; no device is touched), and ends the run with a sentence
where it does not: a program without it would parse a forest of
hundreds of megabytes before saying so.
"""

from __future__ import annotations

import time

import numpy as np

from lib import byname

_PROBE = (
    '<PMML version="4.3"><DataDictionary>'
    '<DataField name="x" optype="continuous" dataType="float"/>'
    '<DataField name="y" optype="categorical" dataType="integer">'
    '<Value value="0"/><Value value="1"/></DataField></DataDictionary>'
    '<MiningModel functionName="classification"><MiningSchema>'
    '<MiningField name="y" usageType="target"/><MiningField name="x"/>'
    '</MiningSchema><Segmentation multipleModelMethod="modelChain">'
    + "".join(
        f'<Segment id="{k}"><True/><MiningModel functionName="regression">'
        '<MiningSchema><MiningField name="x"/></MiningSchema><Output>'
        f'<OutputField name="m{k}" feature="predictedValue"/></Output>'
        '<Segmentation multipleModelMethod="sum"><Segment id="1"><True/>'
        '<TreeModel functionName="regression"><MiningSchema>'
        '<MiningField name="x"/></MiningSchema><Node><True/>'
        '<Node score="1"><SimplePredicate field="x" operator="lessThan" '
        'value="0"/></Node><Node score="2"><True/></Node></Node>'
        '</TreeModel></Segment></Segmentation></MiningModel></Segment>'
        for k in range(2))
    + '<Segment id="3"><True/><RegressionModel functionName="classification"'
    ' normalizationMethod="softmax"><MiningSchema>'
    '<MiningField name="y" usageType="target"/><MiningField name="m0"/>'
    '<MiningField name="m1"/></MiningSchema>'
    + "".join(
        f'<RegressionTable intercept="0" targetCategory="{k}">'
        f'<NumericPredictor name="m{k}" coefficient="1"/></RegressionTable>'
        for k in range(2))
    + "</RegressionModel></Segment></Segmentation></MiningModel></PMML>"
)


def _rank_wire_takes_softmax_chains() -> bool:
    from flink_jpmml_tpu.compile import qtrees
    from flink_jpmml_tpu.pmml import parse_pmml

    match = getattr(qtrees, "_match_chain", None)
    return match is not None and match(parse_pmml(_PROBE)) is not None


if not _rank_wire_takes_softmax_chains():
    # run.py's loader ends the run with this sentence (exit 1)
    raise LookupError(
        "paths/block_classes.py: the program's rank wire takes no softmax "
        "chain, so this configuration has no rank-wire scorer")

_Twin = byname.load("paths", "block_twin").Path


class Path(_Twin):
    def __init__(self, cfg: dict, compiled, addr: dict, on_batch):
        super().__init__(cfg, compiled, addr, on_batch)
        n_warm, C = int(cfg["warmup_records"]), len(self.q.labels)
        self.warm_probs = np.full((n_warm, C), np.nan, np.float32)
        self.warm_labels = np.full(n_warm, -1, np.int32)

        def sink(out, n, first_off):
            with self._annot("bench.sink"):
                top, probs, labels = (np.asarray(a)[:n] for a in out)
                keep = min(n, n_warm - int(first_off))
                if keep > 0:
                    self.warm_probs[first_off:first_off + keep] = probs[:keep]
                    self.warm_labels[first_off:first_off + keep] = (
                        labels[:keep])
                on_batch(int(first_off), int(n), top, time.monotonic())

        self.pipe._sink = sink

    def facts(self) -> dict:
        return dict(super().facts(), classes=len(self.q.labels))
