"""Parallel-ray projection matrix for ART (paper Fig. 12 ``parallelRay``).

Builds the dense system matrix A ∈ R^{(Nproj·Nray) × Nray²}: row (θ, r)
holds the pixel weights of the ray at angle θ and detector offset r,
assembled by sampling along the ray with bilinear interpolation (Joseph-
style). Dense is deliberate: the ART kernel streams rows HBM→VMEM, and a
dense (1, Ncol) row is exactly the MXU/VPU-friendly layout (the paper
itself densifies: ``A = A.todense()``).
"""
from __future__ import annotations

import functools

import numpy as np


@functools.lru_cache(maxsize=8)
def parallel_ray_matrix(nray: int, angles_key: tuple) -> np.ndarray:
    """Rows of all ``nray`` rays of one angle at a time: each ray is sampled
    at ``2·nray`` points, and each sample adds its four bilinear weights
    (times the sample step) into the pixels around it."""
    angles = np.asarray(angles_key, dtype=np.float64)
    n = nray
    nsamp = 2 * n
    ts = np.linspace(-n / 2, n / 2, nsamp)
    offs = np.arange(n) - n / 2 + 0.5
    A = np.zeros((len(angles) * n, n * n), dtype=np.float32)
    step = ts[1] - ts[0]
    ray = np.repeat(np.arange(n), nsamp)              # ray of each sample
    for ai, theta in enumerate(np.deg2rad(angles)):
        d = np.array([np.cos(theta), np.sin(theta)])      # ray direction
        o = np.array([-np.sin(theta), np.cos(theta)])     # detector axis
        # (ray, sample, yx): the sample points of every ray of this angle
        pts = (offs[:, None, None] * o + ts[None, :, None] * d
               + n / 2 - 0.5).reshape(-1, 2)
        ys, xs = pts[:, 0], pts[:, 1]
        y0 = np.floor(ys).astype(int)
        x0 = np.floor(xs).astype(int)
        fy, fx = ys - y0, xs - x0
        block = A[ai * n:(ai + 1) * n]       # this angle's rows, a view
        for dy, dx, wgt in ((0, 0, (1 - fy) * (1 - fx)),
                            (0, 1, (1 - fy) * fx),
                            (1, 0, fy * (1 - fx)),
                            (1, 1, fy * fx)):
            yy, xx = y0 + dy, x0 + dx
            ok = (yy >= 0) & (yy < n) & (xx >= 0) & (xx < n)
            # float32 adds in the per-ray order: each row sums its samples
            # corner by corner, as a ray-at-a-time loop does
            np.add.at(block, (ray[ok], yy[ok] * n + xx[ok]),
                      (wgt[ok] * step).astype(np.float32))
    return A


def make_system(nray: int, angles: np.ndarray) -> np.ndarray:
    return parallel_ray_matrix(nray, tuple(np.asarray(angles).tolist()))


def project(A: np.ndarray, volume: np.ndarray) -> np.ndarray:
    """Forward-project a (Nslice, Nray, Nray) volume -> tilt series
    (Nslice, Nrow) with Nrow = Nproj·Nray."""
    nslice = volume.shape[0]
    flat = volume.reshape(nslice, -1)
    return flat @ A.T
