"""Plain reference for the streaming ptychography cell: RAAR (Luke 2005) with
SHARP's single overlap solve per iteration (paper §III, eqs. 4-7), in numpy,
written from the algorithm and not from the program under test.

Per iteration, over F exit waves ψ of n² at scan positions p_j:

    Fψ = fft2(ψ);  error  e = sqrt(Σ(|Fψ| - m)² / Σ m²)
    ψ₁ = ifft2(m · Fψ / sqrt(|Fψ|² + 1e-12))                    (modulus)
    O  = Σ_j ψ₁_j P* ⊕ p_j / (Σ_j |P|² ⊕ p_j + ε)               (eq. 4)
    P  = Σ_j ψ₁_j O*_j / (Σ_j |O_j|² + ε)   from iteration 2 on (eq. 5)
    π  = P · O_j
    ψ' = 2β π + (1 - 2β) ψ₁ + β (ψ - π)                         (eq. 7)

where ⊕ p_j adds a frame into the object canvas at its position and O_j is
the patch of O there. The waves start as the probe scaled to each frame's
measured power. Frames arrive in batches; each batch appends its frames'
start waves and runs ``iters_per_batch`` iterations over all frames so far,
with the iteration counter running on across batches; after the last batch
``refine`` more iterations run over all frames.

``round_to`` rounds every array the algorithm produces to a lower
precision (a dtype such as ``ml_dtypes.bfloat16``), which makes the
control of the correctness check.
"""
from __future__ import annotations

from typing import Any

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import scipy.fft

BLOCKS = 16          # blocks of frames per iteration


class RAAR:
    """One iteration at a time. Every per-frame part runs on blocks of
    frames in a thread pool (numpy and the FFT release the interpreter
    lock); the sums over frames add the blocks' partial sums."""

    def __init__(self, positions: np.ndarray, n: int,
                 obj_shape: tuple[int, int], beta: float,
                 probe_update_start: int, eps: float,
                 round_to: Any = None, pool: Any = None) -> None:
        self.n, self.obj_shape = n, obj_shape
        self.beta, self.start, self.eps = beta, probe_update_start, eps
        self.round_to = round_to
        self.pool = pool
        r = np.arange(n)
        py = positions[:, 0, None, None] + r[None, :, None]
        px = positions[:, 1, None, None] + r[None, None, :]
        self.flat = (py * obj_shape[1] + px).reshape(len(positions), -1)

    def q(self, z: np.ndarray) -> np.ndarray:
        """Round to the control's precision (identity for the reference)."""
        if self.round_to is None:
            return z
        if np.iscomplexobj(z):
            return (z.real.astype(self.round_to).astype(np.float64)
                    + 1j * z.imag.astype(self.round_to).astype(np.float64))
        return z.astype(self.round_to).astype(np.float64)

    def _blocks(self, fn, F: int) -> list:
        step = max(1, -(-F // BLOCKS))
        spans = [slice(i, min(i + step, F)) for i in range(0, F, step)]
        if self.pool is None:
            return [fn(s) for s in spans]
        return list(self.pool.map(fn, spans))

    def _scatter(self, vals: np.ndarray, rows: slice) -> np.ndarray:
        idx = self.flat[rows].ravel()
        size = self.obj_shape[0] * self.obj_shape[1]
        v = np.broadcast_to(vals, (rows.stop - rows.start,) + vals.shape[-2:]
                            ).reshape(-1)
        if np.iscomplexobj(v):
            return (np.bincount(idx, v.real, size)
                    + 1j * np.bincount(idx, v.imag, size))
        return np.bincount(idx, v, size)

    def init_waves(self, mag: np.ndarray, probe: np.ndarray) -> np.ndarray:
        power = np.sqrt(np.mean(mag * mag, axis=(1, 2)))
        scale = power / (np.mean(np.abs(probe)) * self.n * self.n + 1e-9)
        return self.q(probe[None] * scale[:, None, None])

    def step(self, psi, mag, probe, it):
        q, F, b = self.q, len(psi), self.beta
        psi1 = np.empty_like(psi)

        def modulus_and_object_sums(s):
            far = q(scipy.fft.fft2(psi[s]))
            amp = np.abs(far)
            m = mag[s]
            err, norm = np.sum((amp - m) ** 2), np.sum(m * m)
            psi1[s] = q(scipy.fft.ifft2(q(far * (m / np.sqrt(amp * amp
                                                             + 1e-12)))))
            return (err, norm, self._scatter(psi1[s] * np.conj(probe), s),
                    self._scatter(np.abs(probe) ** 2, s))

        parts = self._blocks(modulus_and_object_sums, F)
        err = sum(p[0] for p in parts)
        norm = sum(p[1] for p in parts)
        num = q(sum(p[2] for p in parts).reshape(self.obj_shape))
        den = q(sum(p[3] for p in parts).reshape(self.obj_shape))
        obj = q(num / (den + self.eps))
        flat_obj = obj.reshape(-1)

        def patches(s):
            return flat_obj[self.flat[s]].reshape(-1, self.n, self.n)

        if it >= self.start:
            def probe_sums(s):
                o = patches(s)
                return (np.sum(psi1[s] * np.conj(o), axis=0),
                        np.sum(np.abs(o) ** 2, axis=0))

            parts = self._blocks(probe_sums, F)
            probe = q(sum(p[0] for p in parts)
                      / (sum(p[1] for p in parts) + self.eps))
        new = np.empty_like(psi)

        def combine(s):
            pi2 = q(probe[None] * patches(s))
            new[s] = q(2 * b * pi2 + (1 - 2 * b) * psi1[s]
                       + b * (psi[s] - pi2))

        self._blocks(combine, F)
        return new, obj, probe, float(np.sqrt(err / max(norm, 1e-12)))


def _pool():
    return ThreadPoolExecutor(min(BLOCKS, os.cpu_count() or 1))


def reconstruct(mags: np.ndarray, positions: np.ndarray, probe0: np.ndarray,
                obj_shape: tuple[int, int], *, beta: float,
                probe_update_start: int, eps: float, batch_frames: int,
                iters_per_batch: int, refine: int, round_to: Any = None,
                batches: int | None = None) -> dict[str, Any]:
    """Run a scan's schedule (its first ``batches`` batches only, when
    given; then no refinement). Returns what it publishes: the error after
    each batch, the final object and error; and, under ``steps``, each
    published iteration (the last of each batch and of the refinement):
    its input waves, probe and iteration number, and its output waves and
    probe."""
    F, n = mags.shape[0], mags.shape[-1]
    with _pool() as pool:
        alg = RAAR(positions, n, obj_shape, beta, probe_update_start, eps,
                   round_to, pool)
        mags = alg.q(np.asarray(mags, np.float64))
        probe = alg.q(np.asarray(probe0, np.complex128))
        psi = np.zeros((0, n, n), np.complex128)
        it, errs, steps = 0, [], []
        starts = list(range(0, F, batch_frames))[:batches]
        for start in starts:
            stop = min(start + batch_frames, F)
            psi = np.concatenate([psi, alg.init_waves(mags[start:stop],
                                                      probe)])
            for i in range(iters_per_batch):
                psi_in, probe_in = psi, probe
                psi, obj, probe, err = alg.step(psi, mags[:stop], probe, it)
                if i == iters_per_batch - 1:
                    steps.append((psi_in, probe_in, it, psi, probe))
                it += 1
            errs.append(err)
        if batches is not None:
            return {"batch_errors": errs, "steps": steps}
        for i in range(refine):
            psi_in, probe_in = psi, probe
            psi, obj, probe, err = alg.step(psi, mags, probe, it)
            if i == refine - 1:
                steps.append((psi_in, probe_in, it, psi, probe))
            it += 1
    return {"batch_errors": errs, "object": obj, "error": err,
            "steps": steps}


def one_step(mags: np.ndarray, positions: np.ndarray, psi: np.ndarray,
             probe: np.ndarray, it: int, obj_shape: tuple[int, int], *,
             beta: float, probe_update_start: int, eps: float
             ) -> tuple[np.ndarray, np.ndarray, np.ndarray, float]:
    """One iteration from given waves and probe over the first
    ``len(psi)`` frames: (waves, object, probe, error)."""
    psi = np.asarray(psi, np.complex128)
    with _pool() as pool:
        alg = RAAR(positions, mags.shape[-1], obj_shape, beta,
                   probe_update_start, eps, None, pool)
        return alg.step(psi, np.asarray(mags[:len(psi)], np.float64),
                        np.asarray(probe, np.complex128), it)
