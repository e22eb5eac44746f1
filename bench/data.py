"""The data set of a benchmark configuration, made from a seed.

The key generator is named by the configuration file's ``data.keys``:
``ycsb_hashed``, YCSB's ``insertorder=hashed`` user IDs, the 64-bit FNV-1a
hash of the record number as YCSB's ``Utils.fnvhash64`` computes it.
Record ``i`` of the load and insert ``j`` of the run are record numbers
``i`` and ``records + j``, as in YCSB's CoreWorkload.

Values are random 32-bit words from the seed, loaded in an order drawn
from the seed. The layout follows ``chip_smoke.make_data``, copied so the
benchmark does not import the smoke run.
"""
from __future__ import annotations

import numpy as np

FNV_OFFSET_BASIS_64 = np.uint64(0xCBF29CE484222325)
FNV_PRIME_64 = np.uint64(1099511628211)


def fnvhash64(recnums: np.ndarray) -> np.ndarray:
    """YCSB's ``Utils.fnvhash64`` over an array of record numbers: FNV-1a
    over the eight low-first bytes, then the absolute value as a signed
    64-bit integer."""
    val = np.asarray(recnums, np.uint64).copy()
    h = np.full(val.shape, FNV_OFFSET_BASIS_64, np.uint64)
    with np.errstate(over="ignore"):
        for _ in range(8):
            h ^= val & np.uint64(0xFF)
            val >>= np.uint64(8)
            h *= FNV_PRIME_64
    neg = h >= np.uint64(1 << 63)
    h[neg] = ~h[neg] + np.uint64(1)  # two's-complement negation
    return h


def check_widths(cfg: dict) -> None:
    """The configuration's stated widths are the ones the store runs:
    8 B keys (two 32-bit words) and ``vw`` 32-bit words of value."""
    if int(cfg["key_bytes"]) != 8:
        raise ValueError("the store's keys are 8 B (KW=2)")
    if int(cfg["value_bytes"]) != 4 * int(cfg["store"]["vw"]):
        raise ValueError("value_bytes is not 4 * vw")


def record_keys(cfg: dict, recnums: np.ndarray) -> np.ndarray:
    """Keys of the given record numbers."""
    kind = cfg["data"]["keys"]
    if kind != "ycsb_hashed":
        raise ValueError(f"unknown key generator {kind!r}")
    return fnvhash64(np.asarray(recnums, np.uint64))


def make_data(cfg: dict, seed: int):
    """The configuration's records: ``(records, order, vals)`` with
    ``records`` the keys by record number, ``order`` the load order (a
    permutation of record numbers) and ``vals`` the values by record
    number."""
    check_widths(cfg)
    rng = np.random.default_rng([seed, 0xDA7A])
    n, vw = int(cfg["records"]), int(cfg["store"]["vw"])
    records = record_keys(cfg, np.arange(n))
    if len(np.unique(records)) != n or not records.all():
        raise RuntimeError("record keys collide or hit 0")
    order = rng.permutation(n)
    vals = rng.integers(0, 1 << 32, size=(n, vw), dtype=np.uint32)
    return records, order, vals
