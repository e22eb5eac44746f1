"""The one traffic generator: reads a mix from ``bench/traffic/<mix>.json``.

A mix is a closed loop (``"loop": "closed"``, the only loop there is) of
``clients`` requests outstanding, each one ``Batch`` of one op, drawn
from ``requests``, a list of request kinds with their ``share``:

- ``scan``: one Seek+NextN from ``key``, with ``length``
  ``{"uniform": [lo, hi]}``;
- ``get``: one lookup of ``key``;
- ``insert``: one new record, keyed by the configuration's generator
  from the next record number, with a value drawn from the seed.

``key`` is ``{"zipfian": theta}``: an existing record chosen by YCSB's
scrambled zipfian with constant ``theta`` over the loaded records and the
mix's ``new_keys`` records inserted next. A draw of a record not yet
inserted is drawn again, as YCSB's CoreWorkload does, so reads reach
inserted keys too.

Every seed issues the same work in another order: each chunk of
``CHUNK`` requests holds each kind in proportion to its ``share``
(rounded by largest remainder), shuffled, and scan lengths are dealt
from a shuffled deck of every length, refilled when it runs out. The
request sequence is a function of the seed alone: clients take requests
from it in order, whichever client is free.
"""
from __future__ import annotations

import dataclasses
import threading

import numpy as np

from bench import data as D
from bench.zipf import ScrambledZipfian

CHUNK = 1024  # requests drawn at a time


@dataclasses.dataclass
class Request:
    kind: str  # "scan", "get" or "insert"
    key: int
    n: int = 0  # scan length
    val: np.ndarray | None = None  # insert value
    check: bool = True  # in the sample the run compares

    @property
    def is_write(self) -> bool:
        return self.kind == "insert"

    def ops(self):
        from repro.db.ops import Op

        if self.kind == "scan":
            return [Op.scan(self.key, self.n)]
        if self.kind == "get":
            return [Op.get(self.key)]
        return [Op.put(self.key, self.val)]


def lengths(spec: dict) -> list[int]:
    """Every scan length a request kind can draw."""
    lo, hi = spec["uniform"]
    return list(range(int(lo), int(hi) + 1))


def apportion(shares: np.ndarray, total: int) -> np.ndarray:
    """Whole counts summing to ``total`` in proportion to ``shares``
    (largest remainder)."""
    exact = shares * total
    counts = np.floor(exact).astype(int)
    rest = np.argsort(counts - exact, kind="stable")[:total - counts.sum()]
    counts[rest] += 1
    return counts


def zipf_theta(spec: dict) -> float:
    (dist, theta), = spec.items()
    if dist != "zipfian":
        raise ValueError(f"unknown key distribution {dist!r}")
    return float(theta)


class Traffic:
    def __init__(self, mix: dict, cfg: dict, records: np.ndarray,
                 seed: int):
        if mix.get("loop") != "closed":
            raise ValueError(f"unknown loop {mix.get('loop')!r}")
        self.mix = mix
        self.cfg = cfg
        self.records = records
        self.vw = int(cfg["store"]["vw"])
        self.kinds = mix["requests"]
        shares = np.array([float(k["share"]) for k in self.kinds])
        self.per_chunk = apportion(shares / shares.sum(), CHUNK)
        self._decks: dict[tuple, list[int]] = {}
        self.rng = np.random.default_rng([seed, 0x7AFF])
        items = len(records) + int(mix.get("new_keys", 0))
        self.zipf = {}
        for k in self.kinds:
            if "key" in k and zipf_theta(k["key"]) not in self.zipf:
                theta = zipf_theta(k["key"])
                self.zipf[theta] = ScrambledZipfian(items, theta, self.rng)
        self._inserts = np.zeros(0, np.uint64)
        self._n_inserts = 0
        self._buf: list[Request] = []
        self._lock = threading.Lock()

    # ---------------- drawing ----------------
    def _insert_key(self, j: int) -> int:
        """Key of the run's insert ``j``."""
        if j >= len(self._inserts):
            n = len(self.records)
            self._inserts = D.record_keys(
                self.cfg, np.arange(n, n + 2 * j + 64))
        return int(self._inserts[j])

    def _existing(self, spec: dict) -> int:
        z = self.zipf[zipf_theta(spec)]
        n = len(self.records)
        while True:
            i = int(z.sample(self.rng, 1)[0])
            if i < n + self._n_inserts:
                break
        return int(self.records[i]) if i < n else self._insert_key(i - n)

    def _length(self, spec: dict) -> int:
        """The next scan length from the spec's shuffled deck."""
        deck = self._decks.setdefault(tuple(spec["uniform"]), [])
        if not deck:
            deck.extend(self.rng.permutation(lengths(spec)).tolist())
        return int(deck.pop())

    def _draw(self, kind: dict) -> Request:
        op = kind["op"]
        if op == "scan":
            n = self._length(kind["length"])
            return Request("scan", self._existing(kind["key"]), n=n)
        if op == "get":
            return Request("get", self._existing(kind["key"]))
        if op == "insert":
            key = self._insert_key(self._n_inserts)
            self._n_inserts += 1
            val = self.rng.integers(0, 1 << 32, self.vw, dtype=np.uint32)
            return Request("insert", key, val=val)
        raise ValueError(f"unknown request kind {op!r}")

    def next(self) -> Request:
        """The next request of the seed's sequence (thread-safe)."""
        with self._lock:
            if not self._buf:
                frac = float(self.mix.get("check_fraction", 1.0))
                picks = self.rng.permutation(
                    np.repeat(np.arange(len(self.kinds)), self.per_chunk))
                checks = self.rng.random(CHUNK) < frac
                self._buf = [
                    dataclasses.replace(self._draw(self.kinds[int(i)]),
                                        check=bool(c))
                    for i, c in zip(picks, checks)
                ][::-1]
            return self._buf.pop()

    # ---------------- warm-up ----------------
    def warmup(self, parts: list[np.ndarray]) -> list[Request]:
        """Requests that reach every shape the mix can use, given the
        loaded keys of each partition (``parts``, ascending): per
        partition, a lookup and each scan length from its first key and
        from its last key (a window that comes back short and runs on
        into the next partition, or ends); and one insert, the first of
        the seed's sequence."""
        out: list[Request] = []
        for kind in self.kinds:
            op = kind["op"]
            for ks in parts:
                if op == "scan":
                    for n in lengths(kind["length"]):
                        out.append(Request("scan", int(ks[0]), n=n))
                        out.append(Request("scan", int(ks[-1]), n=n))
                elif op == "get":
                    out.append(Request("get", int(ks[0])))
            if op == "insert":
                out.append(self._draw(kind))
        return out
