"""A keyed state table with bounded probe windows that admits and evicts,
with nothing of the program in it: plain loops over three arrays, one
entry a slot (the hash that lives there, whether one does, and when the
slot was last touched).

``WindowTable.call`` answers one routing call, the hashes of its
records → where each key lives from now on and whether the call
admitted it (on a zeroed row). The rule, in the deployment's words
(``configs/gbm500_keyed_churn.json`` ``guarantees``):

- a key's window is the ``probe`` slots from ``hash % capacity`` on. A
  key is resident where its window holds its hash before any empty
  slot, and keeps that slot; the call touches it;
- a key that is not resident takes the first empty slot of its window.
  The keys of one call probe together, a slot a round, and of those
  that reach one empty slot in the same round the smallest hash has it;
- a key that is not resident and finds no empty slot evicts: it names
  the slot of its window that was touched longest ago, of those this
  call has not touched; of equal stamps the first on the way from its
  home slot. Of the keys that name one slot the smallest hash has it
  (the slot is then touched by this call), and the others name again
  from what is left of their own windows, until each has a slot;
- only a key whose whole window this call has touched goes to the
  scratch slot (``capacity``): its records are folded nowhere.

What a key's row must hold follows (``expected_rows``): the row its
slot started with plus the tally of all its records if the table never
admitted it, the tally since its last admission alone if it did.
"""

from __future__ import annotations

import numpy as np


class WindowTable:
    def __init__(self, keys, occ, stamp, probe: int):
        """Adopts the three arrays and changes them in place."""
        self.keys, self.occ, self.stamp = keys, occ, stamp
        self.capacity, self.probe = int(keys.shape[0]), int(probe)
        self.scratch = self.capacity
        self.now = int(stamp.max()) if self.capacity else 0
        self.admitted = self.evicted = self.overflowed = 0
        self.thrown_out = []  # the hash each eviction took the slot from

    def window(self, h: int) -> list:
        home = h % self.capacity
        return [(home + p) % self.capacity for p in range(self.probe)]

    def call(self, hashes, held=()) -> dict:
        """One routing call → ``{hash: (slot, admitted)}``. ``held`` are
        slots of records routed earlier and folded by no dispatch yet:
        this call touches them too."""
        keys, occ, stamp = self.keys, self.occ, self.stamp
        self.now += 1
        now = self.now
        for s in held:
            stamp[s] = now
        answer = {}
        # ascending, so that the first to reach a slot in a round is
        # the smallest hash
        pending = sorted(set(int(h) for h in np.asarray(hashes).tolist()))
        for p in range(self.probe):
            still = []
            for h in pending:
                s = (h % self.capacity + p) % self.capacity
                if not occ[s]:
                    occ[s], keys[s], stamp[s] = True, h, now
                    answer[h] = (s, True)
                    self.admitted += 1
                elif keys[s] == h:
                    stamp[s] = now
                    answer[h] = (s, False)
                else:
                    still.append(h)
            pending = still
            if not pending:
                break
        while pending:
            named = {}  # slot → the smallest hash that names it
            for h in pending:
                best = None
                for s in self.window(h):
                    if stamp[s] < now and (
                            best is None or stamp[s] < stamp[best]):
                        best = s
                if best is None:
                    answer[h] = (self.scratch, False)
                    self.overflowed += 1
                else:
                    named.setdefault(best, h)
            for s, h in named.items():
                self.thrown_out.append(int(keys[s]))
                keys[s], stamp[s] = h, now
                answer[h] = (s, True)
                self.admitted += 1
                self.evicted += 1
            pending = [h for h in pending if h not in answer]
        return answer


class Rows:
    """What each key's row must hold, call by call: the tally of the
    records the sink received for it since the table last admitted it,
    and whether it ever did. Keys are told apart by the table's hash."""

    def __init__(self):
        self.count, self.total = {}, {}
        self.admitted = set()  # hashes whose row started from zero
        self.slot = {}         # hash → where the reference has it

    def fold(self, table: WindowTable, hashes, scores) -> None:
        """One routing call and the fold of its records."""
        hashes = np.asarray(hashes).tolist()
        for h, (s, fresh) in table.call(hashes).items():
            if fresh:
                self.admitted.add(h)
                self.count[h], self.total[h] = 0, 0.0
            self.slot[h] = s
        for gone in table.thrown_out:
            # a key of the stream that lost its slot has no row any more
            self.slot.pop(gone, None)
        del table.thrown_out[:]
        for h, v in zip(hashes, np.asarray(scores, np.float64).tolist()):
            if self.slot.get(h, table.scratch) != table.scratch:
                self.count[h] = self.count.get(h, 0) + 1
                self.total[h] = self.total.get(h, 0.0) + v

    def expected(self, hashes, first_rows):
        """→ (count, score sum) the rows of ``hashes`` must hold:
        ``first_rows`` are the rows their slots started with (float64
        ``[n, width]``, column 0 the count, column 1 the sum), which
        count for a key the table never admitted."""
        kept = np.array([h not in self.admitted for h in hashes])
        first = np.where(kept[:, None], np.asarray(first_rows, np.float64), 0.0)
        n = np.array([self.count[h] for h in hashes], np.float64)
        s = np.array([self.total[h] for h in hashes], np.float64)
        return first[:, 0] + n, first[:, 1] + s
