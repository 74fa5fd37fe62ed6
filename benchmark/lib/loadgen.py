"""The load generator: a jax-free child process that holds the Kafka
broker and the producer, so that neither shares the interpreter lock
with the pipeline under test.

Protocol: one JSON object per line on stdin (commands) and stdout
(replies). ``init`` → broker address; ``produce`` appends a stretch at
once (the warm-up stream); ``start`` begins the measured producer;
``delivered`` tells the producer how far the sink is and is answered
with how far the log is, so the harness can take the log's lead itself;
``stop`` ends the producer and returns its own account of the run.

One producer, parameterised by the traffic file alone:
``closed_backlog`` keeps ``produced - delivered`` at ``backlog_records``
and reports the least it saw on any turn after the backlog was first
full (the shape of ``bench._measure_kafka_mode``,
flink_jpmml_tpu/bench.py:491, without its seek back to offset 0:
offsets are fresh and increasing). Every ``delivered`` reply says
whether the backlog has been full yet (``filled``): the harness opens
its window no earlier. ``producer_max_records_per_s``, null in every
cell, holds the producer back, from its start or, with
``producer_max_from: "filled"``, from the moment the backlog was first
full: ``rehearse.py --starve`` proves with the second that a producer
slower than the pipeline fails the run on the log's lead, and with the
first that one that never fills its backlog gets no window at all.

The child pins itself to the cores ``init`` names (``lib/cores.py``:
the rule) before it starts a thread, and runs one encoder a core of
them. The log is trimmed behind the sink: segments wholly under the
delivered offset less one backlog are dropped (``_BulkBroker.trim``),
so the child holds two backlogs of records, not the run's 50-60M (7-8
GB of fresh pages in 30 s, faulted in beside a pipeline that faults in
its own mirror); no consumer of a closed backlog reads behind its sink.

The producer has to out-run the program's own ingest (one prefetch
sidecar fetches and decodes about 4M records/s on a TPU v5e host,
PERF.md §5), or the window measures the producer. The stream's rows are
made at 6M records/s; the native record-batch encode (its CRC32C over
every byte, on the CPU's crc32 instruction where it has one) takes 51 ns
a record on that host and releases the interpreter lock. So one
producer thread decides what to append, as before, and ``ENCODERS``
threads draw and encode it in pieces of ``PIECE`` records, which the
producer publishes in offset order as they come ready, handing out the
next chunk while the last is still being encoded; the keys of the
block after are drawn meanwhile. The log holds the same record at the
same offset in the same segments of 512 whatever the thread count or
the piece size (``benchmark/tests`` decodes it back).

The broker is the program's ``MiniKafkaBroker``. Its public
``append_rows`` keeps a Python ``bytes`` per record (0.2-0.5M records/s
on one core, below what the pipeline drains), so ``_BulkBroker`` stores
encoded segments only; it relies on the broker's ``_mu``, ``_segs`` and
``_next`` (runtime/kafka.py:2040-2054) and says so if they are gone.
"""

from __future__ import annotations

import bisect
import collections
import concurrent.futures
import json
import os
import sys
import threading
import time

import numpy as np

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _BENCH)                   # lib
sys.path.insert(1, os.path.dirname(_BENCH))  # the program's broker

from lib import cores  # noqa: E402
from lib.stream import Stream  # noqa: E402

_SEG = 512  # records per stored record batch (MiniKafkaBroker._SEG_RECORDS)
ENCODERS = 4  # where nothing is pinned; pinned, one a core (lib/cores.py)
PIECE = 16 * _SEG  # records one encoder draws and encodes at a time


def _make_broker(topic: str):
    from flink_jpmml_tpu.runtime import native
    from flink_jpmml_tpu.runtime.kafka import MiniKafkaBroker

    class _BulkBroker(MiniKafkaBroker):
        """Appends pre-encoded segments without the per-record Python
        objects; one producer thread, one partition."""

        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            for attr in ("_mu", "_segs", "_next"):
                if not hasattr(self, attr):
                    raise RuntimeError(
                        f"MiniKafkaBroker has no {attr}: the benchmark's "
                        "bulk append needs a new way in"
                    )
            self.produced = 0

        def publish(self, segs) -> None:
            """Encoded segments that start at the log's head."""
            with self._mu:
                if self._next[0] != self.produced or (
                        segs[0][0] != self.produced):
                    raise RuntimeError("log head moved under the producer")
                self._segs[0].extend(segs)
                self._next[0] = self.produced = segs[-1][1]
                self._mu.notify_all()

        def trim(self, below: int) -> None:
            """Drop the segments that lie wholly under offset ``below``."""
            with self._mu:
                segs = self._segs[0]
                del segs[:bisect.bisect_right(
                    segs, below, key=lambda s: s[1])]

        @staticmethod
        def encode(rows: np.ndarray, base: int):
            """float32 rows of offsets ``base``... → segments of ``_SEG``
            records, ``(first offset, one past last, batch bytes)``. Any
            thread: the native call holds no lock of the interpreter."""
            raw = np.ascontiguousarray(rows, np.float32).view(
                np.uint8).reshape(rows.shape[0], -1)
            segs = []
            for i in range(0, raw.shape[0], _SEG):
                chunk = raw[i:i + _SEG]
                blob = native.kafka_encode_fixed(chunk, base + i)
                if blob is None:
                    raise RuntimeError(
                        "native record-batch encoder unavailable: "
                        f"{native.build_error()}"
                    )
                segs.append((base + i, base + i + chunk.shape[0], blob))
            return segs

    return _BulkBroker(topic=topic)


class Generator:
    def __init__(self, init: dict):
        self.stream = Stream(
            init["seed"], init["n_features"], init["key_domain"],
            init["key_mix"], init["pool_rows"],
        )
        self.broker = _make_broker(init["topic"])
        # one a core the child was pinned to (``main``), else ENCODERS
        self.n_encoders = (cores.encoders_for(len(init["cores"]))
                           if init.get("cores") else ENCODERS)
        self._encoders = concurrent.futures.ThreadPoolExecutor(
            max_workers=self.n_encoders, thread_name_prefix="encode")
        self.filled = False  # the measured producer's backlog has been full
        self._stop = threading.Event()
        self._thread = None
        self._delivered = 0
        self._stats = {}
        self._error = None

    def _piece(self, lo: int, hi: int):
        return self.broker.encode(self.stream.rows(lo, hi), lo)

    def _submit(self, lo: int, hi: int) -> list:
        """Offsets [lo, hi) handed to the encoders → their pieces, in
        offset order. The keys of the block after ``hi`` are drawn
        first, beside the pieces, so that no piece of the next stretch
        waits for them."""
        # (its result is the stream's cache; what it raises, the piece
        # that needs the block raises again)
        self._encoders.submit(self.stream.ranks, hi, hi + 1)
        return [
            self._encoders.submit(self._piece, a, min(a + PIECE, hi))
            for a in range(lo, hi, PIECE)
        ]

    def append(self, lo: int, hi: int) -> None:
        """Offsets [lo, hi) at the log's head, published in order."""
        for piece in self._submit(lo, hi):
            self.broker.publish(piece.result())

    def close(self) -> None:
        self._encoders.shutdown(wait=True, cancel_futures=True)
        self.broker.close()

    def note_delivered(self, n: int) -> None:
        self._delivered = int(n)

    def start(self, traffic: dict, delivered: int) -> dict:
        self._delivered = int(delivered)
        self.filled = False
        target = {"closed_backlog": self._run_closed}[traffic["loop"]]
        t0 = time.monotonic() + 0.05
        self._thread = threading.Thread(
            target=self._guard, args=(target, traffic, t0), daemon=True,
        )
        self._thread.start()
        return {"t0": t0, "first_offset": self.broker.produced}

    def _guard(self, target, traffic, t0) -> None:
        try:
            target(traffic, t0)
        except BaseException as e:  # reported by stop(), which re-raises
            self._error = e

    def _run_closed(self, traffic: dict, t0: float) -> None:
        chunk = int(traffic["chunk_records"])
        want = int(traffic["backlog_records"])
        cap = traffic.get("producer_max_records_per_s")
        cap_from_fill = traffic.get("producer_max_from") == "filled"
        first = submitted = self.broker.produced
        pieces = collections.deque()  # handed to the encoders, unpublished
        least, filled_after, trimmed_to, t_start = None, None, 0, t0
        while not self._stop.is_set():
            while pieces and pieces[0].done():
                self.broker.publish(pieces.popleft().result())
            backlog = self.broker.produced - self._delivered
            if self.filled:
                # every turn counts once the backlog has been full:
                # before that the producer has not yet had its chance
                least = backlog if least is None else min(least, backlog)
            if self._delivered - want >= trimmed_to + chunk:
                trimmed_to = self._delivered - want
                self.broker.trim(trimmed_to)
            held = cap is not None and (self.filled or not cap_from_fill) and (
                submitted - first >= cap * (time.monotonic() - t0)
            )
            if (submitted - self._delivered < want and not held
                    and len(pieces) < 2 * self.n_encoders):
                pieces.extend(self._submit(submitted, submitted + chunk))
                submitted += chunk
                continue
            if not self.filled and backlog >= want:
                self.filled, now = True, time.monotonic()
                filled_after = now - t_start
                if cap_from_fill:  # the hold counts from here
                    first, t0 = submitted, now
            if pieces:
                concurrent.futures.wait([pieces[0]], timeout=0.002)
            else:
                time.sleep(0.002)
        for piece in pieces:
            piece.cancel()
        self._stats = {"least_backlog_records": least,
                       "backlog_full_after_s": filled_after}

    def stop(self) -> dict:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=30.0)
            if self._thread.is_alive():
                raise RuntimeError("producer thread did not stop")
        if self._error is not None:
            raise self._error
        return dict(self._stats, produced=self.broker.produced)


def main() -> None:
    out = sys.stdout
    gen = None

    def reply(obj) -> None:
        out.write(json.dumps(obj) + "\n")
        out.flush()

    try:
        for line in sys.stdin:
            msg = json.loads(line)
            cmd = msg["cmd"]
            if cmd == "init":
                # before the first thread: each inherits the set
                pinned = cores.pin(msg.get("cores"))
                gen = Generator(msg)
                reply({"host": gen.broker.host, "port": gen.broker.port,
                       "pid": os.getpid(), "pinned": pinned,
                       "encoders": gen.n_encoders})
            elif cmd == "produce":
                lo = gen.broker.produced
                gen.append(lo, lo + int(msg["n"]))
                reply({"produced": gen.broker.produced})
            elif cmd == "start":
                reply(gen.start(msg["traffic"], msg["delivered"]))
            elif cmd == "delivered":
                gen.note_delivered(msg["n"])
                reply({"produced": gen.broker.produced,
                       "filled": gen.filled})
            elif cmd == "stop":
                reply(gen.stop())
            elif cmd == "exit":
                break
    finally:
        if gen is not None:
            gen.close()


if __name__ == "__main__":
    main()
