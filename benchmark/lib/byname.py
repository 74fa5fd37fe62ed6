"""Whatever belongs to one configuration, mix or metric is a file of
its own, found by the name a data file gives it: a path builder
(``paths/``), a metric reader (``end_to_end/``, ``layer_metrics/``), a
key mix (``lib/keymix/``), a model kind (``models/``), a warm-up check
(``warmup_checks/``). A later PR adds a file and a name; it edits
nothing."""

from __future__ import annotations

import importlib.util
import os
import re

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]*\Z")


def load(folder: str, name: str):
    """The module ``benchmark/<folder>/<name>.py``; ``LookupError``
    with that path where there is none."""
    path = os.path.join(BENCH, folder, f"{name}.py")
    if not _NAME.match(str(name)) or not os.path.isfile(path):
        raise LookupError(f"no {folder}/{name}.py under benchmark/")
    spec = importlib.util.spec_from_file_location(
        "bench_" + re.sub(r"\W", "_", f"{folder}_{name}"), path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
