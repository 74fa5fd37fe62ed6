"""Key mix ``zipf``: zipf ``a`` folded into the key domain,
``(zipf - 1) % domain``: ``bench.run_stateful_bench``'s mix
(flink_jpmml_tpu/bench.py:2689-2691). Block ``b`` draws from
``default_rng([seed, 1, b])``, so a block never depends on how the
stream was chunked on the wire.

A key mix is one file ``lib/keymix/<kind>.py`` with one function,
``ranks(block, seed, domain, mix, n) -> int64[n]``: the key ranks
(below ``domain``) of the ``n`` records of block ``block``, a pure
function of its arguments; ``mix`` is the traffic file's ``key_mix``.
"""

from __future__ import annotations

import numpy as np


def ranks(block: int, seed: int, domain: int, mix: dict, n: int) -> np.ndarray:
    rng = np.random.default_rng([seed, 1, block])
    return ((rng.zipf(float(mix["a"]), size=n) - 1) % domain).astype(np.int64)
