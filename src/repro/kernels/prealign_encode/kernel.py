"""Fused MODWT pre-alignment + nearest-centroid encode Pallas kernel.

The paper's pre-aligned encode (§3.5 + Alg. 2) is a four-stage pipeline —
Haar MODWT scale recursion, change-point detection, split snapping, segment
re-interpolation — followed by a DTW-1NN scan against every subspace
codebook.  Run as the ``modwt.prealign`` + ``pq.encode`` two-step, the
``(B, M, D/M + t)`` segment tensor round-trips through HBM between the
stages.  This kernel fuses the whole pipeline over one ``(block, L)`` batch
tile, so segments only ever exist in VMEM:

  1. *MODWT scale recursion* — ``level`` shifted adds (circular ``roll``):
     ``v_j = (v_{j-1} + roll(v_{j-1}, 2^{j-1})) / 2``.
  2. *Change points* — sign changes of ``x - v_J``; exact zeros carry the
     previous nonzero sign via a log-depth forward fill (masked rolls), the
     gather-free equivalent of the reference's ``associative_scan``.
  3. *Split snapping* — every interior fixed split ``l = m * (L/M)`` is
     static, so the tail window ``[l - t, l]`` is ``t + 1`` static column
     reads; the right-most change point wins (masked min over offsets).
  4. *Segment gather + linear re-interpolation* — data-dependent boundaries
     become per-row fractional positions; two one-hot lane selects (a
     masked max over the ``L`` lanes, exact) plus a lerp resample each
     segment to the static length ``S = L/M + t``.
  5. *Encode* — the ``(block, K)`` pair block per subspace is swept with the
     band-compressed DTW wavefront shared with :mod:`..dtw_band.kernel`
     (the centroids arrive time reversed, as the wavefront takes them);
     codes are the per-row argmin (first-index tie-break, matching
     ``jnp.argmin``).

Static geometry: ``L``, ``M``, ``K``, ``S``, ``level``, ``tail`` and the
band ``window`` are all trace-time constants — data-dependent boundaries
become *indices*, never shapes, exactly like the reference pipeline.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...core.measures import MeasureArg
from ..dtw_band.kernel import wavefront_compressed

__all__ = ["prealign_encode_kernel", "make_prealign_encode_call"]


def _forward_fill_sign(s: jnp.ndarray, t: jnp.ndarray,
                       length: int) -> jnp.ndarray:
    """Replace zeros in ``s (rows, L)`` by the nearest nonzero value to the
    left (log-depth doubling; positions with no nonzero left stay 0)."""
    shift = 1
    while shift < length:
        moved = jnp.where(t >= shift, jnp.roll(s, shift, axis=1), 0.0)
        s = jnp.where(s == 0.0, moved, s)
        shift *= 2
    return s


def _lane_select(x: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """``take_along_axis(x, idx, axis=1)`` for ``x (rows, L)`` and in-range
    ``idx (rows, S)``, as a one-hot masked max over the ``L`` lanes — exact,
    and it lowers where a lane gather of another shape does not."""
    lanes = jax.lax.broadcasted_iota(jnp.int32, idx.shape + x.shape[1:], 2)
    hit = lanes == idx[:, :, None]
    return jnp.max(jnp.where(hit, x[:, None, :], -jnp.inf), axis=2)


def prealign_encode_kernel(x_ref, c_ref, lin_ref, o_ref, *, length: int,
                           n_sub: int, n_k: int, seg_len: int, level: int,
                           tail: int, window: int, block: int, width: int,
                           measure: MeasureArg = None):
    """``x_ref (block, L)``, ``c_ref (M, K, S)`` centroids time reversed,
    ``lin_ref (1, S)`` -> ``o_ref (block, M)`` int32 codes."""
    L, M, K, S = length, n_sub, n_k, seg_len
    x = x_ref[...].astype(jnp.float32)
    lin = lin_ref[...].astype(jnp.float32)            # linspace(0, 1, S)
    t = jax.lax.broadcasted_iota(jnp.int32, (block, L), 1)

    # -- 1. Haar MODWT scale coefficients (circular boundary) ---------------
    v = x
    for j in range(1, level + 1):
        v = 0.5 * (v + jnp.roll(v, 2 ** (j - 1), axis=1))

    # -- 2. change points: sign changes of x - v, zeros carry previous sign -
    s = _forward_fill_sign(jnp.sign(x - v), t, L)
    prev = jnp.where(t == 0, s[:, 0:1], jnp.roll(s, 1, axis=1))
    change = ((s * prev) < 0.0) & (t > 0)             # (block, L) bool

    # -- 3. snap the static interior splits to the right-most change point --
    seg = L // M
    bounds = [jnp.zeros((block, 1), jnp.int32)]
    for m in range(1, M):
        l = m * seg
        # smallest offset o <= tail with a change point at l - o (selects
        # from the far end inward, so the nearest change point wins)
        first = jnp.full((block, 1), tail + 1, jnp.int32)
        for o in range(tail, -1, -1):
            if l - o >= 1:
                first = jnp.where(change[:, l - o:l - o + 1], o, first)
        bounds.append(jnp.where(first <= tail, l - first, l).astype(jnp.int32))
    bounds.append(jnp.full((block, 1), L, jnp.int32))

    # -- 4 + 5. per subspace: re-interpolate, then DTW-1NN over K centroids -
    for m in range(M):
        start, stop = bounds[m], bounds[m + 1]        # (block, 1) int32
        n = stop - start
        pos = start.astype(jnp.float32) + lin * (n - 1).astype(jnp.float32)
        lo = jnp.clip(jnp.floor(pos).astype(jnp.int32), 0, L - 1)
        hi = jnp.clip(lo + 1, 0, L - 1)
        frac = pos - lo.astype(jnp.float32)
        x_lo = _lane_select(x, lo)                    # (block, S)
        x_hi = _lane_select(x, hi)
        segm = x_lo * (1.0 - frac) + x_hi * frac

        cents = c_ref[m]                              # (K, S) reversed
        a = jnp.broadcast_to(segm[:, None, :], (block, K, S))
        b = jnp.broadcast_to(cents[None, :, :], (block, K, S))
        d = wavefront_compressed(a.reshape(block * K, S),
                                 b.reshape(block * K, S),
                                 length=S, window=window, width=width,
                                 measure=measure)
        d = d.reshape(block, K)
        k_iota = jax.lax.broadcasted_iota(jnp.int32, (block, K), 1)
        dmin = jnp.min(d, axis=1, keepdims=True)
        code = jnp.min(jnp.where(d == dmin, k_iota, K), axis=1, keepdims=True)
        o_ref[:, m:m + 1] = code


def make_prealign_encode_call(n: int, length: int, n_sub: int, n_k: int,
                              seg_len: int, level: int, tail: int,
                              window: int, block: int, width: int,
                              interpret: bool,
                              measure: MeasureArg = None):
    """Build the pallas_call: ``X (n, L)`` tiles x one resident codebook.

    ``n`` must already be padded to a multiple of ``block``; the centroid
    tensor and the interpolation grid are broadcast to every tile.
    """
    kernel = functools.partial(
        prealign_encode_kernel, length=length, n_sub=n_sub, n_k=n_k,
        seg_len=seg_len, level=level, tail=tail, window=window, block=block,
        width=width, measure=measure)
    return pl.pallas_call(
        kernel,
        grid=(n // block,),
        in_specs=[
            pl.BlockSpec((block, length), lambda i: (i, 0)),
            pl.BlockSpec((n_sub, n_k, seg_len), lambda i: (0, 0, 0)),
            pl.BlockSpec((1, seg_len), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block, n_sub), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, n_sub), jnp.int32),
        interpret=interpret,
    )
