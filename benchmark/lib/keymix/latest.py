"""Key mix ``latest``: YCSB core workload D's key arrival, "read latest"
(Cooper et al., SoCC 2010; ``workloads/workloadd`` and
``generator/SkewedLatestGenerator.java`` of
github.com/brianfrankcooper/YCSB). A population that grows at its head
while the traffic follows the head: a share ``insert_share`` of the
records each bring a key nobody has seen, and every other record goes
to ``newest - Z``, ``newest`` the newest key as of that record and
``Z`` zipfian with constant ``zipf_constant`` over the ``loaded`` keys
behind it, by YCSB's own method (Gray et al., "Quickly generating
billion-record synthetic databases": one uniform draw a record,
``zeta(loaded, constant)`` computed once).

A key's rank is its age: the ranks below ``loaded_of(domain)`` exist
when the stream starts (rank 0 the oldest), and the j-th insert of the
stream takes rank ``loaded + j``. Block ``b`` (``n`` records) holds
exactly ``inserts_a_block(mix, n)`` inserts, at positions drawn from
``default_rng([seed, 3, b])``, so the newest rank at the start of a
block is a closed form and no block depends on another, or on how the
stream was chunked on the wire. The mix's parameters say nothing of the
population, so the domain does: one sixteenth of it is kept for the
stream's inserts (``HEADROOM``), and the rest is loaded. A stream that
runs past the domain's end recycles ids: ranks are taken modulo
``domain``, the oldest first.

``ranks(block, seed, domain, mix, n)`` is the key mix's one function
(``lib/keymix/zipf.py`` says what one is); ``loaded_of`` and
``inserts_a_block`` are what a table's fill and a check need to know of
the population without drawing it.
"""

from __future__ import annotations

import functools

import numpy as np

HEADROOM = 16  # one part in this many of the domain is the stream's to insert
_ZETA_HEAD = 1 << 16  # summed term by term; the rest in closed form


def loaded_of(domain: int) -> int:
    """Keys that exist before the first record: the oldest ranks."""
    return max(1, int(domain) - int(domain) // HEADROOM)


def inserts_a_block(mix: dict, n: int) -> int:
    return int(round(float(mix["insert_share"]) * int(n)))


def zeta(n: int, theta: float) -> float:
    """``sum(i ** -theta for i in 1..n)``: the head term by term, the
    tail by Euler-Maclaurin (its next term is below 1e-16 of the sum)."""
    m = min(int(n), _ZETA_HEAD)
    head = float(np.sum(np.arange(1, m + 1, dtype=np.float64) ** -theta))
    if n <= m:
        return head
    n, m = float(n), float(m)
    return head + (
        (n ** (1.0 - theta) - m ** (1.0 - theta)) / (1.0 - theta)
        + 0.5 * (n ** -theta - m ** -theta)
        + theta * (m ** (-theta - 1.0) - n ** (-theta - 1.0)) / 12.0
    )


@functools.lru_cache(maxsize=8)
def _gray(items: int, theta: float):
    """The constants of Gray's method for ``items`` ranks → (zetan,
    alpha, eta, the threshold of the second rank)."""
    zetan = zeta(items, theta)
    zeta2 = 1.0 + 0.5 ** theta
    eta = (1.0 - (2.0 / items) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    return zetan, 1.0 / (1.0 - theta), eta, zeta2


def zipfian(u: np.ndarray, items: int, theta: float) -> np.ndarray:
    """YCSB's ``ZipfianGenerator.nextLong`` for uniform draws ``u`` →
    int64 in ``[0, items)``, 0 the most popular."""
    zetan, alpha, eta, zeta2 = _gray(int(items), float(theta))
    uz = u * zetan
    z = (items * (eta * u - eta + 1.0) ** alpha).astype(np.int64)
    z[uz < zeta2] = 1
    z[uz < 1.0] = 0
    return np.minimum(z, items - 1)


def ranks(block: int, seed: int, domain: int, mix: dict, n: int) -> np.ndarray:
    loaded, k = loaded_of(domain), inserts_a_block(mix, n)
    rng = np.random.default_rng([seed, 3, block])
    insert = np.zeros(n, bool)
    insert[rng.choice(n, size=k, replace=False)] = True
    # the newest rank as of each record, its own insert included
    newest = (loaded - 1 + block * k) + np.cumsum(insert, dtype=np.int64)
    back = zipfian(rng.random(n), loaded, float(mix["zipf_constant"]))
    back[insert] = 0
    return (newest - back) % domain
