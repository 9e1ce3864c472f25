"""The plain reference: an exact priority queue on the host, and the
comparison of a run's served stream against it.

The reference holds the resident multiset as a sorted array of keys and
a map from payload id to key.  For each tick it takes the tick's live
adds, then scores what the queue under test served:

* ``bad_pairs`` -- served (key, id) pairs that are not resident: a key
  altered or invented, a payload swapped, an id served twice;
* ``count_gap`` -- served count against ``min(rm_count, resident)``;
* rank errors -- the served keys, ascending, matched to their copies in
  the sorted union (leftmost equal copy first, as for ties), each minus
  the position an exact queue would have served it from.  An exact
  queue scores 0; a c-relaxed one at most ``c - rm_count``.  This is
  the definition of ``repro.quality.harness.RankErrorMeter``, copied
  so that later changes to the program cannot move it.

After the run, :meth:`Reference.resident_gap` compares the queue's
resident (key, id) pairs with the reference's.

Keys are compared as float32, exactly: the queue stores f32 keys and
moves them without arithmetic.  Nothing here imports the program.
"""

from __future__ import annotations

import numpy as np

#: the program's payload bound: ids ride through f32 arithmetic exactly
#: below 2**24
ID_BOUND = 1 << 24


class Reference:
    """Exact reference queue over (f32 key, payload id) pairs."""

    def __init__(self, id_bound: int = ID_BOUND):
        self._keys = np.empty(0, np.float32)     # sorted resident keys
        self._key_of = np.full(id_bound, np.nan, np.float32)
        self._pending: list = []                 # adds not merged yet
        self.bad_pairs = 0
        self.count_gap = 0
        self.ticks = 0

    def __len__(self) -> int:
        return int(self._keys.size) + sum(a.size for a in self._pending)

    def _merge_pending(self) -> None:
        if self._pending:
            self._keys = np.sort(np.concatenate([self._keys] + self._pending),
                                 kind="stable")
            self._pending = []

    def add(self, keys, ids) -> None:
        """Live adds of one tick (no removes), merged lazily."""
        keys = np.asarray(keys, np.float32)
        ids = np.asarray(ids, np.int64)
        if np.unique(ids).size != ids.size or np.isfinite(self._key_of[ids]).any():
            raise ValueError("payload ids of the adds are not unique among "
                             "resident keys")
        self._key_of[ids] = keys
        self._pending.append(keys)

    def tick(self, add_keys, add_ids, rm_count: int, served_keys,
             served_ids) -> np.ndarray:
        """Score one tick; returns its rank errors (one per served key
        found in the union, ascending by key)."""
        self.ticks += 1
        self.add(add_keys, add_ids)
        served_keys = np.asarray(served_keys, np.float32)
        served_ids = np.asarray(served_ids, np.int64)
        due = min(int(rm_count), len(self))
        self.count_gap += abs(due - served_keys.size)
        if not served_keys.size:
            return np.empty(0, np.int64)
        self._merge_pending()

        # (key, id) pairs: the id must be resident, once, with this key
        ok = (served_ids >= 0) & (served_ids < self._key_of.size)
        ids_ok = np.where(ok, served_ids, 0)
        ok &= self._key_of[ids_ok] == served_keys
        _, first = np.unique(served_ids, return_index=True)
        once = np.zeros(served_ids.size, bool)
        once[first] = True
        ok &= once
        self.bad_pairs += int(served_ids.size - ok.sum())
        self._key_of[served_ids[ok]] = np.nan

        # rank of each served key in the union, as RankErrorMeter does
        served = np.sort(served_keys, kind="stable")
        m = served.size
        lt = np.searchsorted(self._keys, served, side="left")
        occ = np.arange(m) - np.searchsorted(served, served, side="left")
        pos = lt + occ
        inside = pos < self._keys.size
        found = inside.copy()
        found[inside] = self._keys[pos[inside]] == served[inside]
        rank_err = (pos - np.arange(m))[found]
        keep = np.ones(self._keys.size, bool)
        keep[pos[found]] = False
        self._keys = self._keys[keep]
        return rank_err.astype(np.int64)

    def resident_pairs(self):
        """(ids, keys) of every resident pair, by id."""
        ids = np.flatnonzero(np.isfinite(self._key_of))
        return ids, self._key_of[ids]

    def resident_gap(self, keys, ids) -> int:
        """Pairs in which the queue's resident (key, id) multiset and
        the reference's differ (an id held twice counts once per extra
        copy)."""
        keys = np.asarray(keys, np.float32)
        ids = np.asarray(ids, np.int64)
        rid, rkey = self.resident_pairs()
        uid, first, cnt = np.unique(ids, return_index=True, return_counts=True)
        gap = int((cnt - 1).sum())                     # duplicated ids
        ukey = keys[first]
        common, ia, ib = np.intersect1d(uid, rid, assume_unique=True,
                                        return_indices=True)
        same = int((ukey[ia] == rkey[ib]).sum())
        gap += (uid.size - same) + (rid.size - same)
        return gap
