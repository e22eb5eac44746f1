"""The plain reference and the comparison that decides ``correct``.

:class:`Reference` holds the loaded records as one sorted array (values
aligned), copied from ``chip_smoke.Reference``, plus every write the run
acknowledged, each with the client-side times at which it was submitted
and acknowledged. It imports nothing of the program.

Reads are judged by what they return against what the store promised
when they ran. A write acknowledged before a read was submitted must be
visible to it. A write submitted after the read completed must not be.
A write in flight while the read ran may be visible or not. A scan
returns, in ascending order, the first ``n`` live keys at or above its
start; a get returns the newest visible value, or nothing.
"""
from __future__ import annotations

import bisect

import numpy as np

NEG_INF = float("-inf")


class Reference:
    def __init__(self, keys: np.ndarray, vals: np.ndarray):
        order = np.argsort(keys)
        self.keys = np.ascontiguousarray(keys[order])
        self.vals = np.ascontiguousarray(vals[order])
        # key -> [(t_submit, t_ack, value or None for a delete)]
        self.writes: dict[int, list] = {}
        self._wkeys: list[int] = []  # sorted written keys

    def record_write(self, key: int, val, t_sub: float, t_ack: float):
        key = int(key)
        if key not in self.writes:
            self.writes[key] = []
            bisect.insort(self._wkeys, key)
        self.writes[key].append((t_sub, t_ack, val))

    def _base(self, key: int):
        i = int(np.searchsorted(self.keys, np.uint64(key)))
        if i < len(self.keys) and int(self.keys[i]) == key:
            return self.vals[i]
        return None

    def _versions(self, key: int, t_sub: float, t_done: float):
        """``(must, allowed)`` for a read submitted at ``t_sub`` and done
        at ``t_done``: whether the key must be present, and the values it
        may show (``None`` standing for "absent")."""
        base = self._base(key)
        vers = [(NEG_INF, NEG_INF, base)]
        vers += [w for w in self.writes.get(key, ()) if w[0] < t_done]
        # a version is out once a later write, started after it was
        # acknowledged, was itself acknowledged before the read began
        allowed = []
        for s, a, v in vers:
            if not any(s2 > a and a2 < t_sub for s2, a2, _ in vers):
                allowed.append(v)
        must = all(v is not None for v in allowed)
        return must, allowed

    def _written_in(self, lo: int, hi: int) -> list[int]:
        i = bisect.bisect_left(self._wkeys, lo)
        j = bisect.bisect_right(self._wkeys, hi)
        return self._wkeys[i:j]

    def check_scan(self, start: int, n: int, t_sub: float, t_done: float,
                   keys: np.ndarray, vals: np.ndarray) -> bool:
        """Whether a scan's answer is one the store may give."""
        keys = np.asarray(keys, np.uint64)
        if len(keys) > n or (vals is not None and len(vals) != len(keys)):
            return False
        i0 = int(np.searchsorted(self.keys, np.uint64(start)))
        base_k = self.keys[i0:i0 + n]
        top = int(base_k[-1]) if len(base_k) == n else (1 << 64) - 1
        written = self._written_in(int(start), top)
        if not written:  # only loaded records in range: one exact answer
            return (np.array_equal(keys, base_k)
                    and np.array_equal(vals, self.vals[i0:i0 + n]))
        if len(keys) and (np.any(keys[1:] <= keys[:-1])
                          or int(keys[0]) < start):
            return False
        got = {int(k): i for i, k in enumerate(keys.tolist())}
        last = int(keys[-1]) if len(keys) else int(start) - 1
        full = len(keys) == n
        hi = max(top, last)
        i1 = int(np.searchsorted(self.keys, np.uint64(hi), side="right"))
        cand = set(self.keys[i0:i1].tolist())
        cand |= set(self._written_in(int(start), hi))
        for k in cand:
            must, allowed = self._versions(k, t_sub, t_done)
            if k in got:
                v = vals[got[k]]
                if not any(a is not None and np.array_equal(v, a)
                           for a in allowed):
                    return False
            elif must and (k <= last or not full):
                return False
        # every returned key must be a loaded or written key in range
        return all(k in cand for k in got)

    def check_get(self, key: int, t_sub: float, t_done: float,
                  found: bool, val) -> bool:
        if int(key) not in self.writes:
            base = self._base(int(key))
            if base is None:
                return not found
            return bool(found) and np.array_equal(val, base)
        must, allowed = self._versions(int(key), t_sub, t_done)
        if not found:
            return not must
        return any(a is not None and np.array_equal(val, a) for a in allowed)
