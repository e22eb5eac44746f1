"""The traffic generator and the copied reference at a tiny size."""
import numpy as np
import pytest

from bench import data as D
from bench.harness import load_cell
from bench.reference import Reference
from bench.traffic import CHUNK, Traffic, apportion, lengths

SHORT, HEAVY = "alex-ycsb.short-range", "alex-ycsb.read-heavy"


def cell(name, records=4096):
    c = load_cell(name)
    c["cfg"]["records"] = records
    return c


def existing_before(reqs, records):
    """Whether every read's key is a loaded key or one inserted earlier
    in the sequence; and how many reads hit an inserted key."""
    seen, hits = set(records.tolist()), 0
    for r in reqs:
        if r.is_write:
            seen.add(r.key)
        elif r.key not in seen:
            return False, hits
        else:
            hits += r.key not in records
    return True, hits


@pytest.mark.parametrize("name", [SHORT, HEAVY])
def test_sequence_is_a_function_of_the_seed(name):
    c = cell(name)
    records, _, _ = D.make_data(c["cfg"], 2**31 + 5)
    a = Traffic(c["mix"], c["cfg"], records, 2**31 + 5)
    b = Traffic(c["mix"], c["cfg"], records, 2**31 + 5)
    for _ in range(1500):
        x, y = a.next(), b.next()
        assert (x.kind, x.key, x.n, x.check) == (y.kind, y.key, y.n, y.check)
        if x.is_write:
            assert np.array_equal(x.val, y.val)


@pytest.mark.parametrize("name", [SHORT, HEAVY])
def test_every_seed_issues_the_same_work(name):
    c = cell(name)
    shares = [k["share"] for k in c["mix"]["requests"]]
    per_chunk = apportion(np.array(shares) / sum(shares), CHUNK)
    seen = []
    for seed in (2**31 + 21, 7):
        records, _, _ = D.make_data(c["cfg"], seed)
        t = Traffic(c["mix"], c["cfg"], records, seed)
        reqs = [t.next() for _ in range(2 * CHUNK)]
        for i in range(2):
            chunk = reqs[i * CHUNK:(i + 1) * CHUNK]
            assert [sum(r.kind == k["op"] for r in chunk)
                    for k in c["mix"]["requests"]] == per_chunk.tolist()
        ns = [r.n for r in reqs if r.kind == "scan"]
        full = len(ns) // 100 * 100
        assert sorted(ns[:full]) == sorted(lengths({"uniform": [1, 100]})
                                           * (full // 100))
        seen.append([r.kind for r in reqs])
    assert seen[0] != seen[1]


def test_apportion_keeps_the_total():
    assert apportion(np.array([0.95, 0.05]), 1024).tolist() == [973, 51]
    assert apportion(np.array([1 / 3] * 3), 10).sum() == 10


def test_short_range_mix():
    c = cell(SHORT)
    records, _, _ = D.make_data(c["cfg"], 9)
    t = Traffic(c["mix"], c["cfg"], records, 9)
    reqs = [t.next() for _ in range(4000)]
    ins = [r for r in reqs if r.kind == "insert"]
    scans = [r for r in reqs if r.kind == "scan"]
    assert len(ins) + len(scans) == len(reqs)
    assert 0.04 < len(ins) / len(reqs) < 0.06
    ns = [r.n for r in scans]
    assert min(ns) == 1 and max(ns) == 100
    new = np.array([r.key for r in ins], np.uint64)
    assert not np.isin(new, records).any()
    # inserts continue YCSB's record numbering
    assert np.array_equal(new, D.fnvhash64(np.arange(4096, 4096 + len(new))))
    assert existing_before(reqs, records)[0]
    assert 0.4 < np.mean([r.check for r in reqs]) < 0.6


def test_read_heavy_mix():
    c = cell(HEAVY)
    records, _, _ = D.make_data(c["cfg"], 10)
    t = Traffic(c["mix"], c["cfg"], records, 10)
    reqs = [t.next() for _ in range(4000)]
    gets = [r for r in reqs if r.kind == "get"]
    assert 0.93 < len(gets) / len(reqs) < 0.97
    assert len(gets) + sum(r.is_write for r in reqs) == len(reqs)
    assert existing_before(reqs, records)[0]
    assert all(r.check for r in reqs)


def test_reads_reach_inserted_keys_once_inserted():
    c = cell(HEAVY, records=512)
    c["mix"]["requests"][1]["share"] = 0.5
    c["mix"]["new_keys"] = 512
    records, _, _ = D.make_data(c["cfg"], 11)
    t = Traffic(c["mix"], c["cfg"], records, 11)
    ok, hits = existing_before([t.next() for _ in range(3000)], records)
    assert ok and hits > 100


def test_only_a_closed_loop_is_known():
    c = cell(HEAVY)
    c["mix"]["loop"] = "open"
    with pytest.raises(ValueError, match="loop"):
        Traffic(c["mix"], c["cfg"], np.arange(1, 9, dtype=np.uint64), 1)


def test_fnvhash64_matches_ycsb():
    # YCSB Utils.fnvhash64(0) and (1), worked by hand from FNV-1a 64
    def ref(v):
        h = 0xCBF29CE484222325
        for _ in range(8):
            h ^= v & 0xFF
            v >>= 8
            h = (h * 1099511628211) & ((1 << 64) - 1)
        return h if h < 1 << 63 else (1 << 64) - h
    got = D.fnvhash64(np.arange(1000, dtype=np.uint64))
    assert got.tolist() == [ref(v) for v in range(1000)]


@pytest.mark.parametrize("name", [SHORT, HEAVY])
def test_warmup_reaches_lengths_partition_ends_and_an_insert(name):
    c = cell(name)
    records, _, _ = D.make_data(c["cfg"], 3)
    t = Traffic(c["mix"], c["cfg"], records, 3)
    parts = np.array_split(np.sort(records), 3)
    warm = t.warmup(parts)
    assert sum(r.is_write for r in warm) == 1
    for ks in parts:
        mine = [r for r in warm if r.key in (int(ks[0]), int(ks[-1]))]
        if name == SHORT:
            for end in (int(ks[0]), int(ks[-1])):
                assert sorted(r.n for r in mine if r.key == end) == \
                    lengths({"uniform": [1, 100]})
        else:
            assert [r.kind for r in mine] == ["get"]


def test_reference_scan_and_get_with_writes():
    keys = np.array([10, 20, 30, 40], np.uint64)
    vals = np.arange(8, dtype=np.uint32).reshape(4, 2)
    ref = Reference(keys, vals)
    v25 = np.array([7, 7], np.uint32)
    ref.record_write(25, v25, t_sub=1.0, t_ack=2.0)
    ref.record_write(35, v25, t_sub=5.0, t_ack=6.0)
    k = np.array([20, 25, 30], np.uint64)
    v = np.stack([vals[1], v25, vals[2]])
    # acknowledged before the scan: must be there
    assert ref.check_scan(15, 3, 3.0, 4.0, k, v)
    assert not ref.check_scan(15, 3, 3.0, 4.0, k[[0, 2]], v[[0, 2]])
    # in flight during the scan: either answer stands
    assert ref.check_scan(15, 3, 1.5, 4.0, k, v)
    assert ref.check_scan(15, 3, 1.5, 4.0, np.array([20, 30, 40], np.uint64),
                          vals[1:4])
    # submitted after the scan finished: must not be there
    assert not ref.check_scan(15, 3, 0.1, 0.5, k, v)
    # a wrong value, keys out of order, or past the start
    assert not ref.check_scan(15, 3, 3.0, 4.0, k, v[::-1])
    assert not ref.check_scan(15, 3, 3.0, 4.0, k[::-1], v[::-1])
    assert not ref.check_scan(21, 2, 3.0, 4.0, k[:2], v[:2])
    assert ref.check_get(25, 3.0, 4.0, True, v25)
    assert not ref.check_get(25, 3.0, 4.0, False, None)
    assert ref.check_get(35, 5.5, 5.7, False, None)
    assert ref.check_get(40, 0.0, 1.0, True, vals[3])
    assert not ref.check_get(41, 0.0, 1.0, True, vals[3])
    # the exact path: no write in range
    assert ref.check_scan(36, 1, 0.0, 1.0, keys[3:], vals[3:])
    assert not ref.check_scan(36, 1, 0.0, 1.0, keys[2:3], vals[2:3])
