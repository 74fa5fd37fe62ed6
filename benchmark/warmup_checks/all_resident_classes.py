"""Warm-up check ``all_resident_classes``: ``all_resident``'s check of
the state, and the classifier's whole output besides. On a sample of
the warm-up stream drawn from the seed, every class's delivered
probability (the path's ``warm_probs``) against the model kind's plain
reference (``probabilities``), within the kind's tolerance; and the
delivered label (``warm_labels``) against the reference's argmax,
wherever the reference's two largest probabilities lie more than twice
the tolerance apart (closer, either label is within the precision the
configuration states).
"""

from __future__ import annotations

import numpy as np

from lib import byname

SAMPLE = 1024  # warm-up records whose every class is held


def check(run: dict):
    faults, compared = byname.load(
        "warmup_checks", "all_resident").check(run)
    cfg, path, n_warm, log = run["cfg"], run["path"], run["n_warm"], run["log"]
    model = byname.load("models", cfg["model_kind"])
    rng = np.random.default_rng([run["seed"], 3])
    offs = np.sort(rng.choice(n_warm, size=min(SAMPLE, n_warm), replace=False))
    got_p, got_l = path.warm_probs[offs], path.warm_labels[offs]
    missing = int(np.isnan(got_p).any(axis=1).sum() + (got_l < 0).sum())
    if missing:
        faults.append(f"{missing} sampled warm-up records have no class "
                      "probabilities or label")
        return faults, compared + [("warmup_class_rows_missing", missing, 0)]
    handle = model.generate(run["seed"], cfg["model"])
    ref = model.probabilities(handle, run["stream"].rows(0, n_warm)[offs])
    allowed = model.SCORE_ATOL + model.SCORE_RTOL * np.abs(ref)
    miss = float((np.abs(got_p.astype(np.float64) - ref) / allowed).max())
    top2 = np.sort(ref, axis=1)[:, -2:]
    decided = top2[:, 1] - top2[:, 0] > 2 * model.SCORE_ATOL
    wrong = int((got_l[decided] != ref.argmax(axis=1)[decided]).sum())
    log(f"classes: {offs.size} sampled warm-up records x {ref.shape[1]} "
        f"classes, largest miss {miss:.3f} of the tolerance; labels "
        f"{wrong} wrong of {int(decided.sum())} the reference decides")
    if miss > 1.0:
        faults.append("class probabilities differ from the reference")
    if wrong:
        faults.append("labels differ from the reference's")
    return faults, compared + [
        ("warmup_class_prob_miss_over_tol", miss, 1.0),
        ("warmup_labels_differing", wrong, 0),
    ]
