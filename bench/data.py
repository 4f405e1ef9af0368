"""Seeded 3-vs-5 digit data, rendered in bulk.

A vectorised copy of the renderer in ``repro.data.digits`` (stroke
skeletons of a "3" and a "5", a random affine jitter per glyph, a Gaussian
pen on a 28x28 grid, pixel noise), drawn from one ``numpy`` generator so
the same seed gives the same glyph parameters everywhere; the raster is
one jitted call.  The per-sample loop there takes about 6 s at 2^15 rows.

``pixels=16`` area-resamples each 28x28 glyph to 16x16 and maps it to
[-1, 1], the layout of the USPS digits.

A configuration draws its data set from a fixed ``data_seed``;
:func:`shuffled` puts its rows in another order.  The same problem in
another order changes the rounding, and with it def-CG's iterations by a
few: the traffic drivers say which orders a run fits.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST

IMG = 28
N_STROKE = 120
PEN_SIGMA = 0.95
NOISE = 0.06


def _three(m: int) -> np.ndarray:
    t1 = np.linspace(-0.5 * np.pi, 0.5 * np.pi, m // 2)
    upper = np.stack([0.42 + 0.18 * np.cos(t1), 0.32 + 0.14 * np.sin(t1)], 1)
    t2 = np.linspace(-0.5 * np.pi, 0.5 * np.pi, m - m // 2)
    lower = np.stack([0.42 + 0.20 * np.cos(t2), 0.64 + 0.16 * np.sin(t2)], 1)
    return np.concatenate([upper, lower])


def _five(m: int) -> np.ndarray:
    n1 = n2 = m // 4
    n3 = m - n1 - n2
    bar = np.stack([np.linspace(0.30, 0.66, n1), np.full(n1, 0.20)], 1)
    stem = np.stack([np.full(n2, 0.30), np.linspace(0.20, 0.46, n2)], 1)
    t = np.linspace(-0.75 * np.pi, 0.6 * np.pi, n3)
    bowl = np.stack([0.42 + 0.20 * np.cos(t), 0.62 + 0.18 * np.sin(t)], 1)
    return np.concatenate([bar, stem, bowl])


def _area_matrix(src: int, dst: int) -> np.ndarray:
    """(dst, src) weights: each output pixel averages the source pixels it
    covers, by overlap length."""
    edges_s = np.arange(src + 1) / src
    edges_d = np.arange(dst + 1) / dst
    lo = np.maximum(edges_d[:-1, None], edges_s[None, :-1])
    hi = np.minimum(edges_d[1:, None], edges_s[None, 1:])
    return np.maximum(hi - lo, 0.0) * dst


def digits(n: int, seed: int, pixels: int = IMG):
    """``n`` glyphs of the 3-vs-5 task: ``(x, y)`` as float32 device arrays.

    The random draws come from ``numpy`` on the host; the raster is one
    jitted call on the default device.

    ``x`` is ``(n, pixels**2)``, in [0, 1] at 28x28 and in [-1, 1] at any
    other size; ``y`` is +1 for a "3" and -1 for a "5", half of each.
    """
    rng = np.random.default_rng(seed)
    y = rng.permuted(np.repeat([1.0, -1.0], [n - n // 2, n // 2]))
    ang = rng.uniform(-0.26, 0.26, n)
    scale = rng.uniform(0.85, 1.15, n)
    shear = rng.uniform(-0.15, 0.15, n)
    shift = rng.uniform(-2.0 / IMG, 2.0 / IMG, (n, 2))
    noise = rng.standard_normal((n, IMG, IMG), dtype=np.float32) * NOISE

    c, s = np.cos(ang), np.sin(ang)
    # (rot @ shr).T per sample, with shr = [[1, shear], [0, 1]].
    m = np.empty((n, 2, 2))
    m[:, 0, 0], m[:, 0, 1] = c, s
    m[:, 1, 0], m[:, 1, 1] = c * shear - s, s * shear + c
    center = np.array([0.45, 0.48])
    protos = {1.0: _three(N_STROKE) - center, -1.0: _five(N_STROKE) - center}
    pts = np.where(
        (y > 0)[:, None, None], protos[1.0][None], protos[-1.0][None]
    )
    pts = np.einsum("nmk,nkl->nml", pts, m) * scale[:, None, None]
    pts = (pts + center + shift[:, None, :]) * IMG

    area = _area_matrix(IMG, pixels) if pixels != IMG else None
    x = _render(jnp.asarray(pts, jnp.float32), jnp.asarray(noise),
                None if area is None else jnp.asarray(area, jnp.float32))
    return x, jnp.asarray(y, jnp.float32)


def shuffled(x, y, seed):
    """``(x, y, perm)``: the rows in the order ``perm`` that ``seed`` draws."""
    perm = np.random.default_rng(seed).permutation(x.shape[0])
    idx = jnp.asarray(perm)
    return jnp.take(x, idx, axis=0), jnp.take(y, idx), perm


@jax.jit
def _render(pts, noise, area):
    """Gaussian-pen raster of each glyph's stroke points, on the device."""
    grid = jnp.arange(IMG, dtype=jnp.float32) + 0.5
    gx = jnp.exp(-0.5 * (grid - pts[..., 0:1]) ** 2 / PEN_SIGMA**2)
    gy = jnp.exp(-0.5 * (grid - pts[..., 1:2]) ** 2 / PEN_SIGMA**2)
    img = jnp.einsum("nmy,nmx->nyx", gy, gx, precision=HIGHEST)
    img = img / jnp.max(img, axis=(1, 2), keepdims=True)
    img = jnp.clip(img + noise, 0.0, 1.0)
    if area is not None:
        img = jnp.einsum("ay,nyx,bx->nab", area, img, area, precision=HIGHEST)
        img = 2.0 * img - 1.0
    return img.reshape(img.shape[0], -1)
