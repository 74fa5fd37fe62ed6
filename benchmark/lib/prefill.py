"""The state table as a running rank of the fleet holds it: every key of
the rank's domain resident, each row with a history, before the first
record of the run arrives. Made in set-up from the seed, in bulk.

Two halves, as the table itself is split (runtime/state.py):

- **Device values**: one jitted call writes the whole ``[rows, 8]``
  buffer on the device. A slot's first row is a pure function of
  ``(seed, slot)`` (``initial_rows``, the numpy twin the reference
  check reads): a count of 1-8 earlier records of mean 2-6, their sums,
  half that weight of decayed mass, a last-seen stride before offset 0,
  a minimum and a maximum. Count and score sum are small dyadic numbers,
  exact in float32. The scratch row and the padding stay zero, as the
  fold requires (compile/statekernel.py).
- **Host mirror**: the key set's uint32 hashes are placed by linear
  probing from ``hash % capacity``, as ``assign_slots`` would place them
  one batch at a time, but in one sorted pass: in home order a key lands
  on ``max(home, previous position + 1)``, so every slot between a
  key's home and its place is taken and the table's own lookup finds
  it. ``assign_slots`` itself routes 4M keys in seconds and would take
  minutes for 150M; there is no public way to restore a table from
  arrays short of a 6.4 GB ``.npz``, so ``apply_fill`` writes
  ``_keys``/``_occ``/``resident`` and says so if they are gone. It
  also writes each placed slot's LRU stamp (``_touch``, under a routing
  sequence number of its own, ``_seq``), as the admission that put the
  key there would have: a table that traffic had filled has every one
  of those stamps written, and a mirror whose stamps are still the
  untouched zero pages ``np.zeros`` hands out pays a first-touch fault
  a probe inside the window (1.09M of them on the four-chip table: the
  routing call ran at 347 falling to 54 us a thousand over a window's
  first 12 s, PERF.md §5). ``back_mirror`` backs the mirror's pages
  beforehand, beside the plan; every run prints the mirror's resident
  bytes before and after. The few
  keys that would land past the table's end go through the public
  ``assign_slots``, which wraps. The key set is the domain's ranks, the
  same for every seed, so the placement is too.

The table knows a key by the CRC32 of its minimal little-endian bytes
behind ``b"i"`` (``partitioner.stable_hash_vec``: eight dependent table
rounds a key, ~50 s for 150M on one core). CRC32 is affine over GF(2),
so for keys of one byte length it is a constant XOR one table look-up
per 16 bits of key: ``crc32_of_ids`` does that, and ``apply_fill``
holds it to the table's public ``hash_keys`` on a sample of every run.
"""

from __future__ import annotations

import ctypes
import json
import mmap
import zlib

import numpy as np

from . import cores
from . import keys as keys_mod

CHUNK = 1 << 21  # small enough that malloc reuses the work arrays


# -- key ids and their hashes -----------------------------------------------

def ids_of_ranks(ranks: np.ndarray) -> np.ndarray:
    """``keys.rank_to_id`` in integers: the value of the float32 whose
    bit pattern is ``0x4B000000 + rank`` is its 24-bit significand
    shifted by its exponent."""
    bits = np.asarray(ranks, np.int64) + keys_mod._F32_INT_BASE
    return ((bits & 0x7FFFFF) | 0x800000) << ((bits >> 23) - 150)


def _crc_tables(nbytes: int):
    """→ (constant, one 65536-entry uint32 table per 16 bits of key) for
    keys that ride ``nbytes`` little-endian bytes behind ``b"i"``."""
    zero = zlib.crc32(b"i" + bytes(nbytes))
    per_byte = np.array([
        [zlib.crc32(b"i" + bytes(j) + bytes([v]) + bytes(nbytes - 1 - j))
         ^ zero for v in range(256)]
        for j in range(nbytes)
    ], np.uint32)
    pairs = []
    for j in range(0, nbytes, 2):
        hi = per_byte[j + 1] if j + 1 < nbytes else np.zeros(256, np.uint32)
        pairs.append((per_byte[j][None, :] ^ hi[:, None]).reshape(-1))
    return np.uint32(zero), pairs


_TABLES = {}


def crc32_of_ids(ids: np.ndarray) -> np.ndarray:
    """``stable_hash_vec`` for ascending non-negative int64 ids."""
    out = np.empty(ids.shape, np.uint32)
    # a key rides bit_length // 8 + 1 bytes; ascending ids fall into
    # runs of one length
    edges = [0] + [
        int(np.searchsorted(ids, 1 << (8 * b - 1))) for b in range(1, 8)
    ] + [ids.shape[0]]
    for b in range(1, 9):
        lo, hi = edges[b - 1], edges[b]
        if lo == hi:
            continue
        if b not in _TABLES:
            _TABLES[b] = _crc_tables(b)
        zero, pairs = _TABLES[b]
        k = ids[lo:hi]
        crc = pairs[0][k & 0xFFFF]
        for j in range(1, len(pairs)):
            crc ^= pairs[j][(k >> (16 * j)) & 0xFFFF]
        out[lo:hi] = crc ^ zero
    return out


# -- the host mirror ---------------------------------------------------------

def plan_fill(n_keys: int, capacity: int) -> dict:
    """Where each of the first ``n_keys`` ranks of the key domain sits in
    a table of ``capacity`` slots → ``{"hash", "pos", "distinct",
    "shared", "stats"}``, in home-slot order. Numpy alone and no table,
    so the harness runs it on a thread beside the rest of set-up. Work
    arrays are int32 and reused: on the chip's host a fresh page costs
    more than the arithmetic on it."""
    packed = np.empty(n_keys, np.uint64)  # home slot << 32 | hash
    for lo in range(0, n_keys, CHUNK):
        hi = min(lo + CHUNK, n_keys)
        h64 = crc32_of_ids(ids_of_ranks(np.arange(lo, hi))).astype(np.uint64)
        packed[lo:hi] = ((h64 % np.uint64(capacity)) << np.uint64(32)) | h64
    packed.sort()
    first = np.ones(n_keys, bool)  # keys of one hash share a row
    np.not_equal(packed[1:], packed[:-1], out=first[1:])
    home = (packed >> np.uint64(32)).astype(np.int32)
    h = packed.astype(np.uint32)
    del packed
    # in home order a key lands on max(home, previous place + 1); with
    # j the key's index among distinct hashes that is a running maximum
    j = np.cumsum(first, dtype=np.int32)
    j -= 1
    pos = home - j
    np.maximum.accumulate(pos, out=pos)
    pos += j
    disp = np.subtract(pos, home, out=j)
    distinct = int(first.sum())
    stats = {
        "keys": n_keys, "distinct_hashes": distinct,
        "load": distinct / capacity,
        "displacement_mean": float(disp.mean()),
        "displaced_8_or_more": float((disp >= 8).mean()),
        "displaced_16_or_more": float((disp >= 16).mean()),
        "displacement_max": int(disp.max()),
    }
    return {"hash": h, "pos": pos, "distinct": distinct, "stats": stats}


def resident_bytes(arr: np.ndarray):
    """Bytes of ``arr`` that a page of memory backs (``mincore``), or
    None where the host cannot say."""
    page = mmap.PAGESIZE
    lo = arr.ctypes.data // page * page
    n_pages = -(-(arr.ctypes.data + arr.nbytes - lo) // page)
    vec = (ctypes.c_ubyte * n_pages)()
    try:
        libc = ctypes.CDLL(None, use_errno=True)
        if libc.mincore(ctypes.c_void_p(lo), ctypes.c_size_t(n_pages * page),
                        vec) != 0:
            return None
    except (OSError, AttributeError):
        return None
    return int(np.count_nonzero(np.frombuffer(vec, np.uint8) & 1)) * page


def mirror_resident(table):
    """→ resident bytes of the mirror's three arrays, or None."""
    parts = [resident_bytes(getattr(table, a))
             for a in ("_keys", "_occ", "_touch")]
    return None if None in parts else sum(parts)


def _needs(table) -> None:
    for attr in ("_keys", "_occ", "_touch", "_seq", "resident"):
        if not hasattr(table, attr):
            raise RuntimeError(
                f"KeyedStateTable has no {attr}: the benchmark's bulk "
                "fill needs a new way in"
            )


def back_mirror(table, log) -> None:
    """Write the empty table's mirror through, zeros over zeros, so
    that a page of memory backs every part of it before ``apply_fill``
    scatters into it. The harness calls this while ``plan_fill`` still
    runs on its thread, where the main thread would only wait: the
    four-chip mirror's 7.28 GB cost 6 s of ``setup_s`` as first-touch
    faults under the scatters (my chip runs, PR 33), and nothing
    here."""
    _needs(table)
    if table.resident:
        raise RuntimeError("back_mirror is for a table that holds no key")
    rss = cores.rss_bytes()
    for attr in ("_keys", "_occ", "_touch"):
        getattr(table, attr)[:] = 0
    log("table fill: the host mirror written through beside the plan; the "
        f"process's resident set grew by {(cores.rss_bytes() or 0) - (rss or 0)}")


def apply_fill(table, plan: dict, log) -> None:
    """Write a plan into the table's host mirror."""
    _needs(table)
    st = plan["stats"]
    log(f"table fill: {json.dumps(st)}, probe window {table.spec.probe}")
    n_keys = st["keys"]
    sample = np.union1d(
        np.arange(0, n_keys, max(1, n_keys // 4096)), [n_keys - 1]
    )
    sid = keys_mod.rank_to_id(sample)
    if not (np.array_equal(sid, ids_of_ranks(sample))
            and np.array_equal(table.hash_keys(sid), crc32_of_ids(sid))):
        raise RuntimeError(
            "the benchmark's ids or hashes differ from the program's "
            "(keys.rank_to_id, KeyedStateTable.hash_keys)"
        )
    if st["displacement_max"] >= table.spec.probe:
        raise RuntimeError(
            f"a key sits {st['displacement_max']} slots from home and the "
            f"probe window is {table.spec.probe}: the table cannot hold "
            "this key set without evicting"
        )
    h, pos = plan["hash"], plan["pos"]
    n_fit = int(np.searchsorted(pos, table.capacity))  # pos ascends
    before = mirror_resident(table)
    table._keys[pos[:n_fit]] = h[:n_fit]
    table._occ[pos[:n_fit]] = True
    # the stamp an admission leaves: one routing call's sequence number,
    # under every one the run will take (``route`` counts on from it)
    table._seq += 1
    table._touch[pos[:n_fit]] = table._seq
    table.resident += plan["distinct"]
    nbytes = table._keys.nbytes + table._occ.nbytes + table._touch.nbytes
    # (``mincore`` counts a page that was only ever read, the shared
    # zero page, as resident: ``back_mirror``'s line has the process's
    # own resident set, which tells them apart)
    log(f"table fill: the host mirror's resident bytes {before} before and "
        f"{mirror_resident(table)} after, of {nbytes}; slots stamped "
        f"{table._seq}")
    if n_fit < pos.shape[0]:
        # past the table's end: the table's own routing wraps them
        left = np.unique(h[n_fit:])
        table.resident -= left.shape[0]  # assign_slots counts them
        table.assign_slots(left, np.zeros(left.shape[0], np.int64))
        log(f"table fill: {left.shape[0]} keys wrapped to the table's "
            "start through assign_slots")


# -- the device values --------------------------------------------------------

def _mix(xp, slot_u32, seed_u32):
    """A 32-bit integer mix, the same in numpy and jax.numpy."""
    x = slot_u32 * xp.uint32(2654435761) + seed_u32
    x = x ^ (x >> xp.uint32(15))
    x = x * xp.uint32(0x2C1B3C6D)
    x = x ^ (x >> xp.uint32(12))
    x = x * xp.uint32(0x297A2D39)
    return x ^ (x >> xp.uint32(15))


def _rows(xp, slots_u32, seed_u32):
    """``[n, 8]``: column ``c`` of every row selected by the column's
    index, so that the device writes the buffer in one fused pass with
    no column-sized temporaries."""
    f32 = xp.float32
    x = _mix(xp, slots_u32, seed_u32)[:, None]
    count = (xp.uint32(1) + (x & xp.uint32(7))).astype(f32)
    mean = f32(2.0) + (
        (x >> xp.uint32(3)) & xp.uint32(1023)).astype(f32) / f32(256.0)
    dcount = count * f32(0.5)
    last_t = -(xp.uint32(1) + ((x >> xp.uint32(13)) & xp.uint32(63))).astype(f32)
    columns = [  # runtime/state.py COL_COUNT .. COL_MAX
        count, count * mean, count * (mean * mean + f32(0.25)), dcount,
        dcount * mean, last_t, mean - f32(0.5), mean + f32(0.5),
    ]
    col = xp.arange(len(columns), dtype=xp.uint32)[None, :]
    out = columns[0]
    for c in range(1, len(columns)):
        out = xp.where(col == xp.uint32(c), columns[c], out)
    return out


def initial_rows(seed: int, slots: np.ndarray) -> np.ndarray:
    """float32 ``[n, 8]``: what ``device_table`` wrote into ``slots``."""
    with np.errstate(over="ignore"):
        return _rows(np, np.asarray(slots).astype(np.uint32),
                     np.uint32(seed & 0xFFFFFFFF))


def table_program(rows: int, capacity: int):
    """The jitted program that writes the ``[rows, 8]`` float32 buffer.
    The seed is an operand, so one program serves every seed."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(seed_u32):
        slot = jax.lax.iota(jnp.uint32, rows)
        live = (slot < jnp.uint32(capacity))[:, None]
        return jnp.where(live, _rows(jnp, slot, seed_u32), jnp.float32(0.0))

    return make


def device_table(seed: int, rows: int, capacity: int):
    """The table's values, written on the device in one call."""
    return table_program(rows, capacity)(np.uint32(seed & 0xFFFFFFFF))
