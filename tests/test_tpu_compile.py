"""Ahead-of-time compiles of the main-path kernels for a described TPU v5e.

No chip is needed: the TPU compiler is installed, and it compiles for a
topology that is described rather than attached.  Each test lowers one
kernel at the widths the index runs (whole series of length 256 with the
coarse window, and the PQ subspace geometry of ``n_sub=16``), compiles it
with ``interpret=False``, and checks that the program holds a Mosaic kernel
(``tpu_custom_call``).  What the chip's compiler refuses fails here.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library at a time, so every test worker
must collect the same tests and only the one running this file loads it.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.pq import PQConfig
from repro.kernels.dtw_band.ops import dtw_band, dtw_band_cdist
from repro.kernels.lb_cascade.ops import lb_refine
from repro.kernels.pq_adc.ops import adc_lookup, adc_sym_cdist
from repro.kernels.prealign_encode.ops import prealign_encode

L, WINDOW = 256, 25  # whole series, coarse window round(0.1 * 256)
PQ = PQConfig(n_sub=16, codebook_size=256)
M, K = PQ.n_sub, PQ.codebook_size
S, S_WINDOW = PQ.subseq_len(L), PQ.window(L)  # 16 + prealign tail, its band
GEOMETRIES = {"series": (L, WINDOW), "subspace": (S, S_WINDOW)}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    return make


def _assert_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_dtw_band_compiles(shape, geometry):
    n, w = GEOMETRIES[geometry]
    _assert_kernel(
        lambda a, b: dtw_band(a, b, w, interpret=False, lane=128),
        shape(1024, n),
        shape(1024, n),
    )


# all-pairs launches as the served index makes them: (A rows, length,
# window) against 256 lists or codewords
CDIST_GEOMETRIES = {
    **{name: (64, n, w) for name, (n, w) in GEOMETRIES.items()},
    "serving_coarse": (64, L, 26),      # bucket 64, coarse band 0.1 * 256
    "serving_lut": (64, S, S_WINDOW),   # one of the 16 query-LUT launches
    "serving_flush": (4096, L, 26),     # a flush batch's coarse assignment
}


@pytest.mark.parametrize("geometry", sorted(CDIST_GEOMETRIES))
def test_dtw_band_cdist_compiles(shape, geometry):
    rows, n, w = CDIST_GEOMETRIES[geometry]
    _assert_kernel(
        lambda a, b: dtw_band_cdist(a, b, w, interpret=False),
        shape(rows, n),
        shape(256, n),
    )


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_lb_refine_compiles(shape, geometry):
    n, w = GEOMETRIES[geometry]
    rows = shape(1024, n)
    _assert_kernel(
        lambda a, b, u, lo, t: lb_refine(a, b, u, lo, t, w, interpret=False, lane=128),
        rows,
        rows,
        rows,
        rows,
        shape(1024),
    )


def test_adc_lookup_compiles(shape):
    _assert_kernel(
        lambda codes, qlut: adc_lookup(codes, qlut, interpret=False),
        shape(4096, M, dtype=jnp.int32),
        shape(M, K),
    )


def test_adc_sym_cdist_compiles(shape):
    codes = shape(512, M, dtype=jnp.int32)
    _assert_kernel(
        lambda a, b, lut: adc_sym_cdist(a, b, lut, interpret=False),
        codes,
        codes,
        shape(M, K, K),
    )


def test_prealign_encode_compiles(shape):
    _assert_kernel(
        lambda x, c: prealign_encode(
            x,
            c,
            PQ.wavelet_level,
            PQ.tail(L),
            S_WINDOW,
            interpret=False,
            lane=128,
        ),
        shape(256, L),
        shape(M, K, S),
    )
