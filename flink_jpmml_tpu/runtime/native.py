"""ctypes binding for the C++ data plane (_native/fjt_native.cpp).

Builds the shared library on first use with the baked-in ``g++``
(pybind11 isn't in the image, hence the C-plain ABI + ctypes). The source
ships inside the package (``flink_jpmml_tpu/_native/``) so a pip install
carries it; the built ``.so`` is cached under ``$FJT_NATIVE_CACHE``
(default ``~/.cache/flink_jpmml_tpu/native``) — site-packages may be
read-only — and rebuilt whenever the source is newer. Falls back cleanly:
callers check :func:`available` and use the pure-Python
:class:`flink_jpmml_tpu.runtime.queues.BoundedQueue` otherwise — same
semantics, lower throughput.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import pathlib
import subprocess
import threading
from typing import Optional, Tuple

import numpy as np

_SRC = (
    pathlib.Path(__file__).resolve().parent.parent
    / "_native"
    / "fjt_native.cpp"
)


def _lib_path() -> pathlib.Path:
    """Cache name carries the source content hash: the shared ~/.cache
    survives package upgrades/downgrades across venvs, and mtimes are
    unreliable for wheels (often pinned to a fixed epoch) — a stale
    ABI loaded through ctypes would corrupt memory, not error."""
    d = os.environ.get("FJT_NATIVE_CACHE") or os.path.join(
        os.path.expanduser("~"), ".cache", "flink_jpmml_tpu", "native"
    )
    try:
        digest = hashlib.sha256(_SRC.read_bytes()).hexdigest()[:12]
    except OSError:
        digest = "nosrc"
    return pathlib.Path(d) / f"libfjt_native-{digest}.so"


_LIB = _lib_path()

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_build_error: Optional[str] = None


def _build() -> Optional[str]:
    """Compile the shared library; returns an error string or None."""
    _LIB.parent.mkdir(parents=True, exist_ok=True)
    # build to a per-process temp name then atomically replace, so
    # concurrent workers racing the first build never load a half-written
    # library
    tmp = _LIB.with_suffix(f".tmp-{os.getpid()}.so")
    cmd = [
        "g++", "-O3", "-std=c++17", "-shared", "-fPIC",
        "-o", str(tmp), str(_SRC), "-lpthread",
    ]
    try:
        proc = subprocess.run(
            cmd, capture_output=True, text=True, timeout=120
        )
    except (OSError, subprocess.TimeoutExpired) as e:
        return f"g++ invocation failed: {e}"
    if proc.returncode != 0:
        return f"g++ failed:\n{proc.stderr[-2000:]}"
    try:
        os.replace(tmp, _LIB)
    except OSError as e:
        return f"cache install failed: {e}"
    return None


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _build_error
    with _lock:
        if _lib is not None or _build_error is not None:
            return _lib
        if not _SRC.exists():
            _build_error = f"source missing: {_SRC}"
            return None
        # hash-keyed cache name: existence IS validity (see _lib_path)
        if not _LIB.exists():
            err = _build()
            if err is not None:
                _build_error = err
                return None
        try:
            lib = ctypes.CDLL(str(_LIB))
        except OSError as e:
            _build_error = str(e)
            return None
        lib.fjt_ring_create.restype = ctypes.c_void_p
        lib.fjt_ring_create.argtypes = [ctypes.c_uint32, ctypes.c_uint32]
        lib.fjt_ring_destroy.argtypes = [ctypes.c_void_p]
        lib.fjt_ring_close.argtypes = [ctypes.c_void_p]
        lib.fjt_ring_size.restype = ctypes.c_uint32
        lib.fjt_ring_size.argtypes = [ctypes.c_void_p]
        lib.fjt_ring_closed.restype = ctypes.c_int
        lib.fjt_ring_closed.argtypes = [ctypes.c_void_p]
        lib.fjt_ring_push_block.restype = ctypes.c_uint32
        lib.fjt_ring_push_block.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_float),
            ctypes.c_uint64,
            ctypes.c_uint32,
            ctypes.c_int64,
        ]
        lib.fjt_ring_drain.restype = ctypes.c_uint32
        lib.fjt_ring_drain.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.c_uint32,
            ctypes.c_int64,
            ctypes.c_int64,  # idle_timeout_us (-1 = wait indefinitely)
        ]
        for name, code_t in (
            ("fjt_bucketize_u8", ctypes.c_uint8),
            ("fjt_bucketize_u16", ctypes.c_uint16),
        ):
            fn = getattr(lib, name)
            fn.restype = None
            fn.argtypes = [
                ctypes.POINTER(ctypes.c_float),   # X
                ctypes.c_uint64,                  # n
                ctypes.c_uint32,                  # f
                ctypes.POINTER(ctypes.c_float),   # cuts (ragged, concat)
                ctypes.POINTER(ctypes.c_int32),   # offs [f+1]
                ctypes.POINTER(ctypes.c_float),   # repl
                ctypes.POINTER(ctypes.c_uint8),   # has_repl
                ctypes.POINTER(ctypes.c_uint8),   # mask (nullable)
                ctypes.POINTER(code_t),           # out
                ctypes.c_uint32,                  # n_threads
            ]
        for name, code_t in (
            ("fjt_bucketize_pow2_u8", ctypes.c_uint8),
            ("fjt_bucketize_pow2_u16", ctypes.c_uint16),
        ):
            fn = getattr(lib, name)
            fn.restype = None
            fn.argtypes = [
                ctypes.POINTER(ctypes.c_float),   # X
                ctypes.c_uint64,                  # n
                ctypes.c_uint32,                  # f
                ctypes.POINTER(ctypes.c_float),   # cuts [f*L], +inf padded
                ctypes.c_uint32,                  # L (power of two)
                ctypes.POINTER(ctypes.c_float),   # repl
                ctypes.POINTER(ctypes.c_uint8),   # has_repl
                ctypes.POINTER(ctypes.c_uint8),   # mask (nullable)
                ctypes.POINTER(code_t),           # out
                ctypes.c_uint32,                  # n_threads
            ]
        lib.fjt_kafka_encode_fixed.restype = ctypes.c_int64
        lib.fjt_kafka_encode_fixed.argtypes = [
            ctypes.POINTER(ctypes.c_uint8),   # values [n, value_len]
            ctypes.c_int64,                   # n
            ctypes.c_int64,                   # value_len
            ctypes.c_int64,                   # base_offset
            ctypes.POINTER(ctypes.c_uint8),   # out buffer
            ctypes.c_int64,                   # out capacity (bytes)
        ]
        lib.fjt_kafka_decode_fixed.restype = ctypes.c_int64
        lib.fjt_kafka_decode_fixed.argtypes = [
            ctypes.POINTER(ctypes.c_uint8),   # record-set bytes
            ctypes.c_int64,                   # len
            ctypes.c_int64,                   # value_len
            ctypes.POINTER(ctypes.c_uint8),   # out values [cap, value_len]
            ctypes.c_int64,                   # out capacity (records)
            ctypes.POINTER(ctypes.c_int64),   # out offsets [cap]
        ]
        for name in ("fjt_crc32c", "fjt_crc32c_table"):
            fn = getattr(lib, name)
            fn.restype = ctypes.c_uint32
            fn.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.fjt_crc32c_hw.restype = ctypes.c_int
        lib.fjt_crc32c_hw.argtypes = []
        lib.fjt_state_hash_f32.restype = None
        lib.fjt_state_hash_f32.argtypes = [
            ctypes.c_void_p,                  # first record's key (f32)
            ctypes.c_uint64,                  # n
            ctypes.c_int64,                   # row stride (bytes)
            ctypes.POINTER(ctypes.c_uint32),  # out hashes [n]
        ]
        lib.fjt_state_resolve.restype = ctypes.c_uint64  # records left
        lib.fjt_state_resolve.argtypes = [
            ctypes.POINTER(ctypes.c_uint32),  # khash [n]
            ctypes.POINTER(ctypes.c_uint8),   # apply [n]
            ctypes.c_uint64,                  # n
            ctypes.POINTER(ctypes.c_uint32),  # mirror keys [capacity]
            ctypes.POINTER(ctypes.c_uint8),   # mirror occupancy
            ctypes.POINTER(ctypes.c_int64),   # mirror touch (stamped)
            ctypes.c_uint64,                  # capacity
            ctypes.c_uint32,                  # probe
            ctypes.c_int64,                   # seq
            ctypes.POINTER(ctypes.c_int32),   # slots [n] (hits written)
            ctypes.POINTER(ctypes.c_int64),   # out: the records left [n]
            ctypes.POINTER(ctypes.c_uint64),  # out: collided
        ]
        lib.fjt_state_claim.restype = None
        lib.fjt_state_claim.argtypes = [
            ctypes.POINTER(ctypes.c_uint32),  # khash [n]
            ctypes.c_uint64,                  # n
            ctypes.POINTER(ctypes.c_uint32),  # mirror keys (claimed)
            ctypes.POINTER(ctypes.c_uint8),   # mirror occupancy (claimed)
            ctypes.POINTER(ctypes.c_int64),   # mirror touch (stamped)
            ctypes.c_uint64,                  # capacity
            ctypes.c_uint32,                  # probe
            ctypes.c_int64,                   # seq
            ctypes.POINTER(ctypes.c_int32),   # out slots [n]
            ctypes.POINTER(ctypes.c_uint8),   # out reset [n]
            ctypes.POINTER(ctypes.c_uint64),  # out counts [4]
        ]
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


def build_error() -> Optional[str]:
    _load()
    return _build_error


class NativeRing:
    """Bounded MPSC ring of fixed-arity float32 records (the C++ batcher).

    ``push_block`` takes a contiguous ``[n, arity]`` float32 array with
    consecutive source offsets; ``drain`` fills a preallocated batch buffer
    fill-or-deadline and returns (records_view, offsets_view) — zero-copy
    numpy views over reused buffers, valid until the next drain.
    """

    def __init__(self, capacity: int, arity: int, batch_size: int):
        lib = _load()
        if lib is None:
            raise RuntimeError(f"native data plane unavailable: {_build_error}")
        self._lib = lib
        self._arity = arity
        self._handle = lib.fjt_ring_create(capacity, arity)
        if not self._handle:
            raise MemoryError("fjt_ring_create failed")
        self._batch = np.zeros((batch_size, arity), np.float32)
        self._offsets = np.zeros((batch_size,), np.uint64)

    def push_block(
        self, block: np.ndarray, first_offset: int, timeout_us: int = -1
    ) -> int:
        block = np.ascontiguousarray(block, np.float32)
        if block.ndim != 2 or block.shape[1] != self._arity:
            raise ValueError(
                f"block shape {block.shape} != [n, {self._arity}]"
            )
        return self._lib.fjt_ring_push_block(
            self._handle,
            block.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            first_offset,
            block.shape[0],
            timeout_us,
        )

    def drain(
        self, deadline_us: int, idle_timeout_us: int = -1
    ) -> Tuple[np.ndarray, np.ndarray]:
        """``idle_timeout_us >= 0`` bounds the wait for the *first*
        record — an empty return on an open ring then means "idle", so
        the consumer can run control-plane work (dynamic serving's
        Add/Del polling) instead of parking forever."""
        n = self._lib.fjt_ring_drain(
            self._handle,
            self._batch.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            self._offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
            self._batch.shape[0],
            deadline_us,
            idle_timeout_us,
        )
        return self._batch[:n], self._offsets[:n]

    def close(self) -> None:
        self._lib.fjt_ring_close(self._handle)

    @property
    def closed(self) -> bool:
        return bool(self._lib.fjt_ring_closed(self._handle))

    def __len__(self) -> int:
        return self._lib.fjt_ring_size(self._handle)

    def __del__(self):
        handle = getattr(self, "_handle", None)
        if handle:
            self._lib.fjt_ring_destroy(handle)
            self._handle = None


def kafka_encode_fixed(
    values: np.ndarray, base_offset: int
) -> Optional[bytes]:
    """Encode a contiguous ``[n, value_len]`` uint8 array as one
    magic-v2 record batch — byte-identical to the Python
    ``encode_record_batch`` (null keys, no headers, timestamp 0).
    → batch bytes, or ``None`` when the native library is unavailable.
    """
    lib = _load()
    if lib is None:
        return None
    values = np.ascontiguousarray(values, np.uint8)
    n, value_len = values.shape
    cap = 61 + n * (value_len + 26)  # generous per-record framing bound
    out = np.empty((cap,), np.uint8)
    rc = lib.fjt_kafka_encode_fixed(
        values.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        n,
        value_len,
        base_offset,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        cap,
    )
    if rc < 0:
        return None
    return out[: int(rc)].tobytes()


def kafka_decode_fixed(
    buf: bytes, value_len: int
) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """Decode magic-v2 record batches whose values are all ``value_len``
    bytes (the tabular-stream contract) at C speed.

    → ``(offsets int64 [n], values uint8 [n, value_len])``, or ``None``
    when the native library is unavailable OR the record set is not
    fixed-length (caller falls back to the Python decoder). Raises
    ``ValueError`` on CRC mismatch / bad magic / malformed framing with
    the same messages as ``decode_record_batches``.
    """
    lib = _load()
    if lib is None:
        return None
    # a record costs at least 6 framing bytes + the value, so this bounds
    # the record count from the buffer size alone
    cap = len(buf) // (value_len + 6) + 1
    out = np.empty((cap, value_len), np.uint8)
    offs = np.empty((cap,), np.int64)
    src = np.frombuffer(buf, np.uint8)  # zero-copy, read-only view
    rc = lib.fjt_kafka_decode_fixed(
        src.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        len(buf),
        value_len,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        cap,
        offs.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
    )
    if rc == -3:
        return None  # not fixed-length: the general Python path decides
    if rc == -1:
        raise ValueError("record batch CRC32C mismatch")
    if rc == -2:
        raise ValueError("unsupported record-batch magic")
    if rc < 0:
        raise ValueError(f"malformed record batch (native rc={rc})")
    n = int(rc)
    return offs[:n].copy(), out[:n].copy()


def _crc32c_with(fn_name: str, data) -> Optional[int]:
    lib = _load()
    if lib is None:
        return None
    buf = np.frombuffer(data, np.uint8)  # zero-copy, read-only view
    return int(getattr(lib, fn_name)(buf.ctypes.data, buf.size))


def crc32c(data) -> Optional[int]:
    """CRC32C of ``data`` as both Kafka codecs compute it: on the CPU's
    crc32 instruction where the host has SSE4.2 (:func:`crc32c_impl`),
    else through the byte table. None when the library is missing."""
    return _crc32c_with("fjt_crc32c", data)


def crc32c_table(data) -> Optional[int]:
    """The byte-table CRC32C alone, whatever the host (the fallback,
    kept callable so tests hold both to the Python oracle)."""
    return _crc32c_with("fjt_crc32c_table", data)


def crc32c_impl() -> Optional[str]:
    """Which CRC32C the library chose at load: ``"sse4.2"`` or
    ``"table"``; None when the library is missing."""
    lib = _load()
    if lib is None:
        return None
    return "sse4.2" if lib.fjt_crc32c_hw() else "table"


def bucketize(
    X: np.ndarray,
    cuts_flat: np.ndarray,
    offs: np.ndarray,
    repl: np.ndarray,
    has_repl: np.ndarray,
    out_dtype,
    mask: Optional[np.ndarray] = None,
    n_threads: int = 0,
) -> Optional[np.ndarray]:
    """Ragged-table rank-wire featurization (branchless per-feature
    lower_bound). The skew-robust fallback: memory and per-feature
    search depth follow each feature's OWN cut count, so one long table
    doesn't tax the others (cf. :func:`bucketize_pow2`). Returns the
    [n, f] code array, or None when the native library is unavailable
    (caller falls back to numpy searchsorted — identical semantics).
    """
    lib = _load()
    if lib is None:
        return None
    X = np.ascontiguousarray(X, np.float32)
    n, f = X.shape
    out = np.empty((n, f), out_dtype)
    fn = lib.fjt_bucketize_u8 if out.itemsize == 1 else lib.fjt_bucketize_u16
    code_t = ctypes.c_uint8 if out.itemsize == 1 else ctypes.c_uint16
    if mask is not None:
        mask = np.ascontiguousarray(mask, np.uint8)
        mask_ptr = mask.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))
    else:
        mask_ptr = ctypes.POINTER(ctypes.c_uint8)()
    fn(
        X.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        n,
        f,
        cuts_flat.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        offs.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        repl.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        has_repl.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        mask_ptr,
        out.ctypes.data_as(ctypes.POINTER(code_t)),
        n_threads,
    )
    return out


def bucketize_pow2(
    X: np.ndarray,
    cuts_padded: np.ndarray,
    L: int,
    repl: np.ndarray,
    has_repl: np.ndarray,
    out_dtype,
    mask: Optional[np.ndarray] = None,
    n_threads: int = 0,
) -> Optional[np.ndarray]:
    """Lockstep rank-wire featurization over +inf-padded [f, L] tables
    (L a power of two) — ~1.3-2x the ragged path on one core when cut
    counts are balanced, because the per-feature binary-search loads
    pipeline instead of serializing. Every feature pays L-depth rounds
    and L-width memory, so heavily skewed tables belong on
    :func:`bucketize` instead (QuantizedWire.encode picks). Same results
    as :func:`bucketize`; None when the library is missing.
    """
    lib = _load()
    if lib is None:
        return None
    X = np.ascontiguousarray(X, np.float32)
    n, f = X.shape
    out = np.empty((n, f), out_dtype)
    fn = (
        lib.fjt_bucketize_pow2_u8
        if out.itemsize == 1
        else lib.fjt_bucketize_pow2_u16
    )
    code_t = ctypes.c_uint8 if out.itemsize == 1 else ctypes.c_uint16
    if mask is not None:
        mask = np.ascontiguousarray(mask, np.uint8)
        mask_ptr = mask.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))
    else:
        mask_ptr = ctypes.POINTER(ctypes.c_uint8)()
    fn(
        X.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        n,
        f,
        cuts_padded.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        L,
        repl.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        has_repl.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        mask_ptr,
        out.ctypes.data_as(ctypes.POINTER(code_t)),
        n_threads,
    )
    return out


def _ptr(a: np.ndarray, ctype):
    return a.ctypes.data_as(ctypes.POINTER(ctype))


def state_hash_f32(X: np.ndarray, key_col: int) -> Optional[np.ndarray]:
    """Column ``key_col`` of a raw ``[n, arity]`` float32 block → uint32
    stable hashes, read where the block lies (any strides): equal to
    ``stable_hash_vec(X[:, key_col].astype(np.int64))`` on every value,
    the ones numpy casts to ``INT64_MIN`` included. None when ``X`` is
    no such block (the caller hashes with numpy). Callers check
    :func:`available` first."""
    if (not isinstance(X, np.ndarray) or X.ndim != 2
            or X.dtype != np.float32):
        return None
    n = X.shape[0]
    out = np.empty(n, np.uint32)
    if n:
        _load().fjt_state_hash_f32(
            X.ctypes.data + key_col * X.strides[1], n, X.strides[0],
            _ptr(out, ctypes.c_uint32),
        )
    return out


def state_resolve(
    khash: np.ndarray, apply: np.ndarray, keys: np.ndarray,
    occ: np.ndarray, touch: np.ndarray, probe: int, seq: int,
    slots: np.ndarray,
) -> Tuple[np.ndarray, int]:
    """One pass of ``KeyedStateTable.route`` over a batch: every applied
    record whose key is found in its probe window, before any empty
    slot, gets its slot written into ``slots`` and stamped ``seq`` in
    ``touch``. → ``(todo int64[k], collided)``: the indices of the
    records left to the caller's rounds, ascending, and the keys found
    past their home slot. The arrays are the table's own (contiguous
    uint32 / bool / int64); callers check :func:`available` first."""
    n = khash.shape[0]
    todo = np.empty(n, np.int64)
    collided = ctypes.c_uint64(0)
    k = _load().fjt_state_resolve(
        _ptr(khash, ctypes.c_uint32), _ptr(apply, ctypes.c_uint8), n,
        _ptr(keys, ctypes.c_uint32), _ptr(occ, ctypes.c_uint8),
        _ptr(touch, ctypes.c_int64), keys.shape[0], probe, seq,
        _ptr(slots, ctypes.c_int32), _ptr(todo, ctypes.c_int64),
        ctypes.byref(collided),
    )
    return todo[:k], int(collided.value)


def state_claim(
    khash: np.ndarray, keys: np.ndarray, occ: np.ndarray,
    touch: np.ndarray, probe: int, seq: int,
) -> Tuple[np.ndarray, np.ndarray, Tuple[int, int, int, int]]:
    """``KeyedStateTable._claim_rounds`` in one native call: its rounds
    and its eviction rule over the records ``khash`` (contiguous uint32),
    claiming and evicting in the table's own arrays. → ``(slots int32[n],
    reset bool[n], (inserts, evictions, overflows, collided))``, the
    scratch slot spelled ``capacity``. Callers check :func:`available`
    first."""
    n = khash.shape[0]
    slots = np.empty(n, np.int32)
    reset = np.empty(n, bool)
    counts = np.zeros(4, np.uint64)
    _load().fjt_state_claim(
        _ptr(khash, ctypes.c_uint32), n, _ptr(keys, ctypes.c_uint32),
        _ptr(occ, ctypes.c_uint8), _ptr(touch, ctypes.c_int64),
        keys.shape[0], probe, seq, _ptr(slots, ctypes.c_int32),
        _ptr(reset, ctypes.c_uint8), _ptr(counts, ctypes.c_uint64),
    )
    return slots, reset, tuple(int(c) for c in counts)
