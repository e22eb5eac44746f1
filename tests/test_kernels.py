"""Per-kernel validation: shape/dtype sweeps against the ref.py oracles.

Kernels run in interpret mode on CPU (the kernel body executes in Python);
the same pallas_call lowers to Mosaic on a real TPU.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import keys as K
from repro.core import query as Q
from repro.core.remix import build_remix
from repro.core.runs import make_run
from repro.core.view import NEWEST_BIT, PLACEHOLDER
from repro.kernels import ops
from repro.kernels.anchor_search import anchor_search
from repro.kernels.ref import anchor_search_ref, selector_decode_ref
from repro.kernels.selector_decode import selector_decode


def random_selectors(rng, q, d, r, pad_prob=0.2):
    """Random selector tiles with tail placeholders + newest bits."""
    sel = rng.integers(0, r, size=(q, d)).astype(np.int32)
    newest = rng.random((q, d)) < 0.7
    sel = sel | (newest.astype(np.int32) << 7)
    n_pad = rng.integers(0, max(1, int(d * pad_prob)), size=q)
    for i in range(q):
        if n_pad[i]:
            sel[i, d - n_pad[i] :] = PLACEHOLDER
    cursors = rng.integers(0, 1000, size=(q, r)).astype(np.int32)
    return jnp.asarray(sel), jnp.asarray(cursors)


@pytest.mark.parametrize("d", [8, 16, 32, 64])
@pytest.mark.parametrize("r", [1, 3, 8, 16])
def test_selector_decode_sweep_d_r(d, r):
    rng = np.random.default_rng(d * 100 + r)
    for q in (1, 5, 128, 300):
        sel, cur = random_selectors(rng, q, d, r)
        got = selector_decode(sel, cur, r=r, interpret=True)
        want = selector_decode_ref(sel, cur, r=r)
        for g, w, name in zip(got, want, ("runid", "absidx", "newest", "pad")):
            np.testing.assert_array_equal(
                np.asarray(g), np.asarray(w), err_msg=f"{name} d={d} r={r} q={q}"
            )


@pytest.mark.parametrize("dtype", [jnp.uint8, jnp.int32])
def test_selector_decode_dtypes(dtype):
    rng = np.random.default_rng(7)
    sel, cur = random_selectors(rng, 64, 32, 4)
    got = selector_decode(sel.astype(dtype), cur, r=4, interpret=True)
    want = selector_decode_ref(sel.astype(jnp.int32), cur, r=4)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("g", [1, 7, 500, 5000])
@pytest.mark.parametrize("kw", [1, 2, 3])
def test_anchor_search_sweep(g, kw):
    rng = np.random.default_rng(g + kw)
    anchors = np.sort(
        rng.integers(0, 2**31, size=(g,)).astype(np.uint64)
    )
    a = np.zeros((g, kw), np.uint32)
    a[:, -1] = anchors & 0xFFFFFFFF
    if kw >= 2:
        a[:, -2] = anchors >> 32
    a = a[np.lexsort([a[:, w] for w in range(kw - 1, -1, -1)])]
    queries = np.concatenate(
        [
            rng.integers(0, 2**31, size=63).astype(np.uint64),
            anchors[rng.integers(0, g, size=17)],  # exact hits
            np.array([0, 2**31 - 1], np.uint64),
        ]
    )
    qa = np.zeros((queries.shape[0], kw), np.uint32)
    qa[:, -1] = queries & 0xFFFFFFFF
    if kw >= 2:
        qa[:, -2] = queries >> 32
    got = anchor_search(jnp.asarray(a), jnp.asarray(qa), interpret=True)
    want = anchor_search_ref(jnp.asarray(a), jnp.asarray(qa))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_anchor_search_block_sweeps():
    rng = np.random.default_rng(11)
    a = np.sort(rng.integers(0, 10**6, size=1000).astype(np.uint64))
    a = K.pack_u64(np.unique(a))
    qs = K.pack_u64(rng.integers(0, 10**6, size=333).astype(np.uint64))
    want = anchor_search_ref(jnp.asarray(a), jnp.asarray(qs))
    for bq in (32, 256):
        for bg in (64, 512):
            got = anchor_search(
                jnp.asarray(a), jnp.asarray(qs), block_q=bq, block_g=bg,
                interpret=True,
            )
            np.testing.assert_array_equal(
                np.asarray(got), np.asarray(want), err_msg=f"bq={bq} bg={bg}"
            )


def _runset(rng, r=6, n=300, space=4000, d=32):
    runs = [
        make_run(
            np.sort(rng.choice(space, size=n, replace=False)).astype(np.uint64),
            seq=i,
        )
        for i in range(r)
    ]
    return build_remix(runs, d=d)


@pytest.mark.parametrize("d", [16, 32, 64])
def test_ops_seek_get_scan_match_reference(d):
    rng = np.random.default_rng(d)
    remix, runset = _runset(rng, d=d)
    queries = rng.integers(0, 4100, size=200).astype(np.uint64)
    qk = jnp.asarray(K.pack_u64(queries))
    np.testing.assert_array_equal(
        np.asarray(ops.seek(remix, runset, qk, interpret=True)),
        np.asarray(Q.seek(remix, runset, qk)),
    )
    f1, v1 = ops.get(remix, runset, qk, interpret=True)
    f2, v2 = Q.get(remix, runset, qk)
    np.testing.assert_array_equal(np.asarray(f1), np.asarray(f2))
    np.testing.assert_array_equal(
        np.asarray(v1)[np.asarray(f1)], np.asarray(v2)[np.asarray(f2)]
    )
    k1, vv1, va1, _ = ops.scan(remix, runset, qk[:32], width=50, interpret=True)
    k2, vv2, va2, _ = Q.scan(remix, runset, qk[:32], width=50)
    np.testing.assert_array_equal(np.asarray(va1), np.asarray(va2))
    np.testing.assert_array_equal(
        np.asarray(k1)[np.asarray(va1)], np.asarray(k2)[np.asarray(va2)]
    )


def test_interpret_decided_from_backend():
    assert ops.interpret_for_backend("cpu") is True
    assert ops.interpret_for_backend("tpu") is False
    with pytest.raises(RuntimeError, match="gpu"):
        ops.interpret_for_backend("gpu")
