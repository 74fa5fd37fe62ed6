"""Which cores the load generator gets, and which the pipeline keeps.

One rule, in one place: of the cores this process may run on
(``os.sched_getaffinity(0)``), the producer's child takes the
highest-numbered third, at most ``PRODUCER_MOST``, and the process that
holds the chip keeps the rest. Both pin themselves
(``os.sched_setaffinity``) before they start a thread, so every thread
of theirs inherits the set: the producer's encoders cannot take a core
from the score thread, the sidecar or the ring feeder, and those cannot
hold the producer under the rate the cell needs. With fewer than
``LEAST_CORES`` nothing is pinned, and the run says so.

On the chip's hosts: 13 cores are 9 for the pipeline and 4 for the
producer; the four-chip host's 30 are 20 and 10.
"""

from __future__ import annotations

import os

LEAST_CORES = 4     # under this a split leaves one side without a core to spare
PRODUCER_MOST = 12  # the producer's threads cannot use more


def split(allowed) -> dict:
    """→ ``{"pipeline": [...], "producer": [...], "why": sentence}``;
    both lists are empty where nothing is pinned."""
    cores = sorted(int(c) for c in allowed)
    n = len(cores)
    if n < LEAST_CORES:
        return {"pipeline": [], "producer": [], "why": (
            f"{n} core(s) allowed, under {LEAST_CORES}: nothing is pinned, "
            "the producer and the pipeline share them")}
    k = min(PRODUCER_MOST, max(1, n // 3))
    return {"pipeline": cores[:-k], "producer": cores[-k:], "why": (
        f"{n} cores allowed: the pipeline keeps {n - k}, the producer's "
        f"child takes the highest {k}")}


def allowed() -> list:
    return sorted(os.sched_getaffinity(0))


def pin(cores) -> bool:
    """The calling thread, and every thread it starts from now on."""
    if not cores:
        return False
    os.sched_setaffinity(0, set(cores))
    return True


def encoders_for(n_producer_cores: int) -> int:
    """Encoder threads of a producer pinned to that many cores: one a
    core, and at most 8, where one producer thread stops keeping them
    fed."""
    return max(1, min(8, n_producer_cores))


def rss_bytes(pid="self"):
    """Resident set size of a process, or None where /proc has none."""
    try:
        with open(f"/proc/{pid}/statm", encoding="ascii") as fh:
            return int(fh.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
    except (OSError, ValueError, IndexError):
        return None
