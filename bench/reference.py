"""Plain reference of the served index's semantics, written from the paper.

Imports nothing of the program.  Everything here is straightforward
``jax.numpy`` on the device, in the precision the caller names
(``float32`` for the reference, ``bfloat16`` for the control):

* banded squared DTW by an anti-diagonal dynamic programme;
* MODWT (Haar) pre-alignment of a series into ``M`` segments of static
  length ``D // M + tail`` (paper section 3.5);
* Keogh envelopes, LB_Kim and reversed LB_Keogh;
* the quantizers the benchmark hands to the program (Euclidean k-means
  from the seed: the benchmark's "weights").

The comparison that uses these lives in ``bench/check.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PAIR_BLOCK = 32768  # pairs per DTW launch of the reference


# ---------------------------------------------------------------------------
# Banded DTW
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("window", "dtype"))
def _dtw_pairs(A, B, *, window, dtype):
    """Squared DTW of pairs ``A[p]``, ``B[p]`` (both ``(P, L)``) under a
    Sakoe-Chiba band ``|i - j| <= window``, one anti-diagonal per step."""
    A = A.astype(dtype)
    B = B.astype(dtype)
    P, L = A.shape
    inf = jnp.array(jnp.inf, dtype)
    brev = jnp.concatenate(
        [jnp.zeros((P, L), dtype), B[:, ::-1], jnp.zeros((P, L), dtype)], axis=1
    )
    i = jnp.arange(L)

    def shift_right(x):  # x[i - 1], inf at i = 0
        return jnp.concatenate([jnp.full((P, 1), inf), x[:, :-1]], axis=1)

    def step(carry, d):
        prev2, prev1 = carry  # diagonals d - 2 and d - 1, indexed by i
        j = d - i
        bj = jax.lax.dynamic_slice_in_dim(brev, 2 * L - 1 - d, L, axis=1)
        cost = (A - bj) ** 2
        best = jnp.minimum(jnp.minimum(shift_right(prev1), prev1), shift_right(prev2))
        best = jnp.where((d == 0) & (i == 0), jnp.zeros((), dtype), best)
        ok = (j >= 0) & (j < L) & (jnp.abs(i - j) <= window)
        cur = jnp.where(ok[None, :], cost + best, inf)
        return (prev1, cur), None

    init = (jnp.full((P, L), inf), jnp.full((P, L), inf))
    (_, last), _ = jax.lax.scan(step, init, jnp.arange(2 * L - 1))
    return last[:, L - 1].astype(jnp.float32)


def dtw_pairs(A, B, window, dtype=jnp.float32):
    """Squared banded DTW of row pairs, in blocks of ``PAIR_BLOCK``."""
    A = jnp.asarray(A, jnp.float32)
    B = jnp.asarray(B, jnp.float32)
    P = A.shape[0]
    if P == 0:
        return np.zeros((0,), np.float32)
    out = []
    for s in range(0, P, PAIR_BLOCK):
        a, b = A[s : s + PAIR_BLOCK], B[s : s + PAIR_BLOCK]
        n = a.shape[0]
        if n < PAIR_BLOCK and P > PAIR_BLOCK:
            pad = PAIR_BLOCK - n
            a = jnp.pad(a, ((0, pad), (0, 0)))
            b = jnp.pad(b, ((0, pad), (0, 0)))
        out.append(np.asarray(_dtw_pairs(a, b, window=int(window), dtype=dtype))[:n])
    return np.concatenate(out)


def dtw_cdist(A, B, window, dtype=jnp.float32):
    """All-pairs squared DTW ``(len(A), len(B))``."""
    A = jnp.asarray(A, jnp.float32)
    B = jnp.asarray(B, jnp.float32)
    na, nb = A.shape[0], B.shape[0]
    ia = jnp.repeat(jnp.arange(na), nb)
    ib = jnp.tile(jnp.arange(nb), na)
    return dtw_pairs(A[ia], B[ib], window, dtype).reshape(na, nb)


# ---------------------------------------------------------------------------
# MODWT pre-alignment (paper section 3.5)
# ---------------------------------------------------------------------------


def _modwt_points(x, level):
    """Segment points: sign changes of ``x - v_J``, ``v_J`` the Haar MODWT
    scaling coefficients (a dyadic circular moving average)."""
    v = x
    for j in range(1, level + 1):
        v = 0.5 * (v + jnp.roll(v, 2 ** (j - 1), axis=-1))
    s = jnp.sign(x - v)
    # an exact zero keeps the sign before it
    s = jax.lax.associative_scan(lambda a, b: jnp.where(b == 0, a, b), s, axis=-1)
    prev = jnp.concatenate([s[..., :1], s[..., :-1]], axis=-1)
    change = (s * prev) < 0
    return change.at[..., 0].set(False)


@functools.partial(jax.jit, static_argnames=("n_sub", "level", "tail"))
def prealign(X, *, n_sub, level, tail):
    """``X (N, D)`` -> ``(N, n_sub, D // n_sub + tail)``: each interior split
    ``l = m * D // n_sub`` moves to the right-most segment point in
    ``[l - tail, l]`` (position >= 1), and every segment is resampled
    linearly to the static length."""
    X = jnp.asarray(X, jnp.float32)
    N, D = X.shape
    seg = D // n_sub
    out_len = seg + tail
    pts = _modwt_points(X, level)
    bounds = [jnp.zeros((N,), jnp.int32)]
    for m in range(1, n_sub):
        l = m * seg
        snapped = jnp.full((N,), l, jnp.int32)
        # from the left edge of the window up to l: the right-most hit wins
        for off in range(tail, -1, -1):
            c = l - off
            if c >= 1:
                snapped = jnp.where(pts[:, c], c, snapped)
        bounds.append(snapped)
    bounds.append(jnp.full((N,), D, jnp.int32))
    bounds = jnp.stack(bounds, axis=1)  # (N, n_sub + 1)
    start, stop = bounds[:, :-1], bounds[:, 1:]
    n = (stop - start)[..., None]
    pos = start[..., None] + jnp.linspace(0.0, 1.0, out_len) * (n - 1)
    lo = jnp.clip(jnp.floor(pos).astype(jnp.int32), 0, D - 1)
    hi = jnp.clip(lo + 1, 0, D - 1)
    frac = pos - lo
    rows = jnp.arange(N)[:, None, None]
    return X[rows, lo] * (1.0 - frac) + X[rows, hi] * frac


# ---------------------------------------------------------------------------
# Envelopes and lower bounds
# ---------------------------------------------------------------------------


def envelope(C, window):
    """Keogh envelope of ``C (..., S)``: max / min over ``|shift| <= window``
    (truncated at the ends)."""
    S = C.shape[-1]
    ups, los = [C], [C]
    for s in range(1, min(window, S - 1) + 1):
        pad = jnp.full(C.shape[:-1] + (s,), -jnp.inf)
        ups += [
            jnp.concatenate([C[..., s:], pad], -1),
            jnp.concatenate([pad, C[..., :-s]], -1),
        ]
        pad = jnp.full(C.shape[:-1] + (s,), jnp.inf)
        los += [
            jnp.concatenate([C[..., s:], pad], -1),
            jnp.concatenate([pad, C[..., :-s]], -1),
        ]
    return jnp.max(jnp.stack(ups), 0), jnp.min(jnp.stack(los), 0)


@jax.jit
def lower_bounds(segs, cents, upper, lower):
    """``segs (N, M, S)`` vs ``cents (M, K, S)`` -> ``(N, M, K)``:
    ``max(LB_Kim, LB_Keogh against the centroid's envelope)``."""
    q = segs[:, :, None, :]
    kim = (q[..., 0] - cents[None, ..., 0]) ** 2 + (q[..., -1] - cents[None, ..., -1]) ** 2
    above = jnp.where(q > upper[None], (q - upper[None]) ** 2, 0.0)
    below = jnp.where(q < lower[None], (lower[None] - q) ** 2, 0.0)
    return jnp.maximum(kim, jnp.sum(above + below, -1))


# ---------------------------------------------------------------------------
# The benchmark's quantizers
# ---------------------------------------------------------------------------


def _kmeans(key, X, k, iters):
    """Euclidean Lloyd iterations from ``k`` distinct sample rows; an empty
    cluster keeps its centroid."""
    init = jax.random.choice(key, X.shape[0], (k,), replace=False)
    C = X[init]

    def body(C, _):
        d = (
            jnp.sum(X * X, 1)[:, None]
            - 2.0 * jnp.dot(X, C.T, precision="highest")
            + jnp.sum(C * C, 1)[None, :]
        )
        a = jnp.argmin(d, 1)
        one = jax.nn.one_hot(a, k, dtype=X.dtype)
        cnt = one.sum(0)
        s = jnp.dot(one.T, X, precision="highest")
        return jnp.where(cnt[:, None] > 0, s / jnp.maximum(cnt, 1.0)[:, None], C), None

    C, _ = jax.lax.scan(body, C, None, length=iters)
    return C


@functools.partial(
    jax.jit,
    static_argnames=("n_lists", "n_sub", "k", "level", "tail", "window", "iters"),
)
def make_quantizers(key, X, *, n_lists, n_sub, k, level, tail, window, iters):
    """Coarse centroids ``(n_lists, D)`` and codebook centroids with their
    envelopes ``(M, K, S)`` from the training rows ``X``."""
    kc, kf = jax.random.split(key)
    coarse = _kmeans(kc, X, n_lists, iters)
    segs = prealign(X, n_sub=n_sub, level=level, tail=tail)
    keys = jax.random.split(kf, n_sub)
    cents = jax.vmap(lambda kk, s: _kmeans(kk, s, k, iters), in_axes=(0, 1))(keys, segs)
    upper, lower = envelope(cents, window)
    return coarse, cents, upper, lower


@functools.partial(jax.jit, static_argnames=("n", "length"))
def random_walks(key, *, n, length):
    """z-normalised Gaussian random walks ``(n, length)``."""
    x = jnp.cumsum(jax.random.normal(key, (n, length), jnp.float32), axis=1)
    mu = x.mean(1, keepdims=True)
    sd = x.std(1, keepdims=True)
    return (x - mu) / jnp.maximum(sd, 1e-9)
