"""Plain reference for the streaming tomography cell: ART (Kaczmarz's
row-action method, paper §IV Fig. 12) in float64 numpy, written from the
algorithm and not from the program under test.

The system matrix is TomViz's ``parallelRay`` projector, one ray at a time:
ray ``r`` at angle ``θ`` is sampled at ``2·n`` points along its direction,
and each sample adds its four bilinear weights, times the sample step, into
the pixels around it. Row ``θ·n + r`` of ``A`` holds that ray.

A slice is reconstructed from ``f = 0`` by ``sweeps`` passes over the rows
in order:

    f ← f + β (b_j − ⟨A_j, f⟩) / ‖A_j‖² · A_j        (rows with ‖A_j‖ > 0)

``round_to`` rounds the matrix, the row and every image the sweep makes to
a lower precision (a dtype such as ``ml_dtypes.bfloat16``), which makes the
control of the correctness check.
"""
from __future__ import annotations

from typing import Any

import numpy as np


def system_matrix(nray: int, angles_deg: np.ndarray) -> np.ndarray:
    """The dense parallel-ray matrix, (len(angles)·nray, nray²), float64."""
    n = nray
    ts = np.linspace(-n / 2, n / 2, 2 * n)
    step = ts[1] - ts[0]
    offs = np.arange(n) - n / 2 + 0.5
    A = np.zeros((len(angles_deg) * n, n * n))
    for ai, theta in enumerate(np.deg2rad(np.asarray(angles_deg, float))):
        d = np.array([np.cos(theta), np.sin(theta)])       # along the ray
        o = np.array([-np.sin(theta), np.cos(theta)])      # across the rays
        for ri, r in enumerate(offs):
            pts = r * o + ts[:, None] * d + n / 2 - 0.5
            y0 = np.floor(pts[:, 0]).astype(int)
            x0 = np.floor(pts[:, 1]).astype(int)
            fy, fx = pts[:, 0] - y0, pts[:, 1] - x0
            row = A[ai * n + ri]
            for dy, dx, w in ((0, 0, (1 - fy) * (1 - fx)),
                              (0, 1, (1 - fy) * fx),
                              (1, 0, fy * (1 - fx)),
                              (1, 1, fy * fx)):
                yy, xx = y0 + dy, x0 + dx
                ok = (yy >= 0) & (yy < n) & (xx >= 0) & (xx < n)
                np.add.at(row, yy[ok] * n + xx[ok], w[ok] * step)
    return A


def art(A: np.ndarray, b: np.ndarray, beta: float, sweeps: int,
        round_to: Any = None) -> np.ndarray:
    """One slice's image from its sinogram row ``b``: (nray²,) float64."""
    def q(x):
        if round_to is None:
            return x
        return np.asarray(x).astype(round_to).astype(np.float64)

    A, b = q(A), q(np.asarray(b, np.float64))
    rip = np.einsum("ij,ij->i", A, A)
    f = np.zeros(A.shape[1])
    for _ in range(sweeps):
        for j in np.flatnonzero(rip > 0):
            row = A[j]
            f = q(f + (beta * (b[j] - row @ f) / rip[j]) * row)
    return f
