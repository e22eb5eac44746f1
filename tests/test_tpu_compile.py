"""Compile-only checks of the device read path for one TPU v5e chip.

The kernels of the served read path (anchor search, selector decode and
the fused ``ops.get_live`` / ``ops.scan_live`` compositions) are lowered
through Mosaic and compiled for a described, not attached, v5e chip at
the shapes ``chip_smoke.py`` drives: batch-256 gets, batch-64 scans of
width 75 (Seek+Next50 plus the store's window slack), group size 32,
16 padded runs of 65536 rows and 32768 groups per partition. The
cursor's packed windows (``db.cursor.window_buffer``: the seek fused
with the first window, and a later window from a saved position) are
compiled as the cursor launches them over a device view — one query and
one packed uint32 host argument carrying ``now`` — at widths 75 and 150,
for both residency tiers (``full``, and ``index``, whose values stay on
the host and whose window also returns each slot's run and row). Nothing
runs; the compiler refuses what the chip would refuse (VMEM overruns,
unsupported primitives, misaligned tiles), which interpret mode cannot
show. The topology is described inside a fixture so that only the test
worker given this file loads the TPU compiler.
"""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core.remix import Remix
from repro.core.runs import RunSet
from repro.db.cursor import window_buffer
from repro.kernels import ops
from repro.kernels.anchor_search import anchor_search
from repro.kernels.selector_decode import selector_decode

Q_GET, Q_SCAN, SCAN_WIDTH = 256, 64, 75
D, R, NMAX, G, VW, KW = 32, 16, 65536, 32768, 4, 2


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs go to /tmp
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but never read back without one: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile_has_kernel(fn, *args, kernel: bool = True):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert ("tpu_custom_call" in text) == kernel


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("g", [512, G], ids=["single_level", "two_level"])
def test_anchor_search_compiles(one_chip, g):
    _compile_has_kernel(
        lambda a, q: anchor_search(a, q, interpret=False),
        _sds(one_chip, (g, KW), jnp.uint32),
        _sds(one_chip, (Q_GET, KW), jnp.uint32),
    )


def test_selector_decode_compiles(one_chip):
    _compile_has_kernel(
        lambda s, c: selector_decode(s, c, r=R, interpret=False),
        _sds(one_chip, (Q_GET, D), jnp.uint8),
        _sds(one_chip, (Q_GET, R), jnp.int32),
    )


def _view(sharding, vw=VW):
    s = lambda shape, dt: _sds(sharding, shape, dt)  # noqa: E731
    remix = Remix(
        anchors=s((G, KW), jnp.uint32), cursors=s((G, R), jnp.int32),
        selectors=s((G * D,), jnp.uint8), n_entries=s((), jnp.int32), d=D,
    )
    runset = RunSet(
        keys=s((R, NMAX, KW), jnp.uint32), vals=s((R, NMAX, vw), jnp.uint32),
        seq=s((R, NMAX), jnp.uint32), tomb=s((R, NMAX), jnp.bool_),
        lens=s((R,), jnp.int32),
    )
    return remix, runset, s((R, NMAX), jnp.uint32), s((), jnp.uint32)


def test_fused_get_and_scan_compile(one_chip):
    remix, runset, exp, now = _view(one_chip)
    lowered = ops.get_live.lower(
        remix, runset, exp, _sds(one_chip, (Q_GET, KW), jnp.uint32), now,
        interpret=False,
    )
    # one packed buffer: per query found, VW value words, run id and row
    out = lowered.out_info
    assert (out.shape, out.dtype) == ((Q_GET * (1 + VW + 2),), jnp.uint32)
    assert "tpu_custom_call" in lowered.compile().as_text()
    _compile_has_kernel(
        lambda rm, rs, e, q, t: ops.scan_live(
            rm, rs, e, q, t, width=SCAN_WIDTH, interpret=False
        ),
        remix, runset, exp, _sds(one_chip, (Q_SCAN, KW), jnp.uint32), now,
    )


@pytest.mark.parametrize("width", [SCAN_WIDTH, 2 * SCAN_WIDTH])
@pytest.mark.parametrize("seek", [True, False], ids=["fused_open", "window"])
@pytest.mark.parametrize("tier", ["full", "index"])
def test_cursor_window_buffer_compiles(one_chip, width, seek, tier):
    rows = tier == "index"
    remix, runset, exp, _ = _view(one_chip, vw=1 if rows else VW)
    # the one host argument: the start key's words (or the position),
    # then the stream's now
    at = _sds(one_chip, (1, KW + 1 if seek else 2), jnp.uint32)
    lowered = window_buffer.lower(remix, runset, exp, at, width=width,
                                  seek=seek, rows=rows, interpret=False)
    words = KW + runset.vals.shape[-1] + 1 + 2 * rows
    assert lowered.out_info.shape == (width * words + 1,)
    assert "tpu_custom_call" in lowered.compile().as_text()


@pytest.mark.parametrize("q,width", [(32, 32), (32, 160), (64, 160)])
def test_scan_live_group_shapes_compile(one_chip, q, width):
    """The padded shapes a coalesced group of scans launches
    (``DeviceViewManager.scan_windows``: a power-of-two query count and
    the width in whole groups of D)."""
    remix, runset, exp, now = _view(one_chip)
    _compile_has_kernel(
        lambda rm, rs, e, qk, t: ops.scan_live(
            rm, rs, e, qk, t, width=width, interpret=False
        ),
        remix, runset, exp, _sds(one_chip, (q, KW), jnp.uint32), now,
    )
