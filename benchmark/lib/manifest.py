"""The rules ``BENCHMARK.json`` keeps so that a later cell comes in by
files and entries alone. ``manifest_faults`` states them as rules, not
as counts: a new configuration's cell, appended to ``workloads`` and
added to the lists of the metrics it reports, breaks none of them.

- every name in a ``workloads`` list is a cell;
- every per-layer metric has a list (a metric without one is owed by
  every cell that reports its end-to-end metric, later ones too);
- a metric named ``<x>.<tag>``, for any tag but ``sat`` (the shared
  one), lists one cell alone, and that cell's configuration is named
  for the tag: ``.mesh`` the mesh cell, ``.churn`` the churn cell, and
  a later deployment's own tag its cell, with no edit here;
- every other list that names the one-chip cell names the churn cell:
  the two run the same compiled program;
- the mesh cell follows the one-chip cell in ``workloads``, and the
  churn cell follows the mesh cell;
- ``setup_s`` has no list: every cell reports it;
- every cell a per-layer metric lists reports the end-to-end metric it
  ``moves`` (a quantity read in cells that report different end-to-end
  metrics is split: ``route_us_per_krec.sat`` moves ``records_per_s``,
  ``route_us_per_krec.mesh`` moves ``records_per_s.mesh``);
- each configuration's ``reduced`` is its file's.
"""

from __future__ import annotations

import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

ONE_CHIP = "gbm500_keyed.kafka_saturated"
MESH = "gbm500_keyed_mesh4.kafka_saturated"
CHURN = "gbm500_keyed_churn.kafka_saturated"
ORDER = (ONE_CHIP, MESH, CHURN)  # each after the one before it


def load_config(entry: dict) -> dict:
    with open(os.path.join(ROOT, entry["file"]), encoding="utf-8") as fh:
        return json.load(fh)


def manifest_faults(manifest: dict, load=load_config) -> list[str]:
    """→ one sentence per broken rule, naming the rule and where it
    broke; empty where the manifest keeps them all. ``load(entry)`` is
    the configuration file of a ``configs`` entry."""
    faults = []
    cells = [w["name"] for w in manifest["workloads"]]
    config_of = {w["name"]: w["config"] for w in manifest["workloads"]}
    # end-to-end metric → the cells that report it
    reported = {m["name"]: m.get("workloads", cells)
                for m in manifest["end_to_end"]}
    for group in ("end_to_end", "per_layer"):
        for m in manifest[group]:
            where, lists = f"{group} {m['name']}", m.get("workloads")
            if lists is None:
                if group == "per_layer":
                    faults.append(f"{where}: no workloads list")
                continue
            if m["name"] == "setup_s":
                faults.append(f"{where}: has a workloads list")
            faults += [f"{where}: lists {n}, which is no cell"
                       for n in lists if n not in cells]
            _, dot, tag = m["name"].rpartition(".")
            if dot and tag != "sat":
                if len(lists) != 1 or tag not in config_of.get(lists[0], ""):
                    faults.append(f"{where}: a .{tag} metric lists {lists}, "
                                  f"not one cell of a {tag} configuration")
            elif ONE_CHIP in lists and CHURN not in lists:
                faults.append(f"{where}: lists {ONE_CHIP} but not {CHURN}")
            if group == "per_layer" and not any(
                    f.startswith(f"{where}:") for f in faults):
                # a metric's first broken rule is the one named
                moved = m.get("moves")
                silent = [n for n in lists
                          if n not in reported.get(moved, ())]
                if silent:
                    faults.append(f"{where}: moves {moved}, which {silent} "
                                  f"do not report")
    for before, after in zip(ORDER, ORDER[1:]):
        if before not in cells or after not in cells:
            faults.append(f"workloads: {before} and {after} are not both cells")
        elif cells.index(after) < cells.index(before):
            faults.append(f"workloads: {after} comes before {before}")
    for entry in manifest["configs"]:
        where = f"configs {entry['name']}"
        try:
            stated = load(entry).get("reduced")
        except OSError as e:
            faults.append(f"{where}: {entry['file']} cannot be read: {e}")
            continue
        if stated is None or set(stated) != set(entry["reduced"]):
            faults.append(f"{where}: reduced {entry['reduced']}, its file "
                          f"{entry['file']} says {stated}")
    return faults
