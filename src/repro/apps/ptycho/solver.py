"""Distributed RAAR ptychographic solver (the SHARP program, paper §III).

Per iteration (SHARP schedule — one overlap solve per iteration):

  1. π₁ (modulus):  ψ₁ = F⁻¹[ mag · Fψ / |Fψ| ]          (Pallas kernel)
  2. overlap update (eqs. 4–5): new probe P and object O from ψ₁ — the
     partial sums Σψ_jO*, Σ|O|², Σψ_jP*, Σ|P|² are *framewise independent*,
     so frames shard across workers and the sums combine with
     MPI_Allreduce ≡ ``jax.lax.psum`` (paper Fig. 9). The object sums are
     placed on the canvas by one Pallas kernel (``overlap_scatter``); the
     probe sums gather the object patches in XLA.   (Pallas scatter, products)
  3. π₂ψ₁ = P·O_patch  with the updated P, O.
  4. RAAR combine (eq. 7): ψ ← 2βπ₂π₁ψ + (1-2β)π₁ψ + β(ψ-π₂ψ)
     with π₂ψ ≈ π₂π₁ψ under the fixed-(P,O) projector — SHARP's
     single-overlap approximation.                           (Pallas kernel)

``raar_step`` is a pure function usable three ways: single-device (tests),
``shard_map`` over a worker mesh (the Spark-MPI bridge path — the paper's
deployment), and inside the streaming pipeline (frames arriving in
micro-batches).

Each phase of a step runs under a ``jax.named_scope``: ``raar/far_field``
(the FFTs and the error sums), ``raar/modulus`` (step 1's projection),
``raar/object_solve`` and ``raar/probe_solve`` (step 2), ``raar/exit_waves``
(step 3) and ``raar/combine`` (step 4). The scopes are metadata: the
compiled program keeps its ops and fusions, and a profiler trace gives each
op's time to its phase on all three paths.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.apps.ptycho.sim import PtychoProblem
from repro.kernels.modulus import ops as modulus_ops
from repro.kernels.overlap import ops as overlap_ops
from repro.kernels.raar import ops as raar_ops


@dataclass
class SolverConfig:
    beta: float = 0.75
    iterations: int = 100
    probe_update_start: int = 2     # iterations of object-only updates first
    eps: float = 1e-6
    use_pallas: bool | None = None  # None = auto by backend


def _phase(name: str):
    """Name scope of one phase of the iteration, ``raar/<name>``. It is
    metadata only: it reaches each compiled op's ``op_name`` and, in a
    profiler trace, the op's ``tf_op``, so device time splits by phase
    (``far_field``, ``modulus``, ``object_solve``, ``probe_solve``,
    ``exit_waves``, ``combine``) on every path that calls ``raar_step``."""
    return jax.named_scope(f"raar/{name}")


def _patch_indices(positions: jax.Array, frame: int):
    iy = positions[:, 0, None, None] + jnp.arange(frame)[None, :, None]
    ix = positions[:, 1, None, None] + jnp.arange(frame)[None, None, :]
    return iy, ix


def overlap_update(psi: jax.Array, positions: jax.Array, probe: jax.Array,
                   obj_shape: tuple[int, int], eps: float = 1e-6,
                   axis_name: str | None = None,
                   update_probe: bool = True,
                   obj_prev: jax.Array | None = None,
                   use_pallas: bool | None = None
                   ) -> tuple[jax.Array, jax.Array]:
    """Eqs. (4)–(5): closed-form O and P from exit waves ψ.

    The object sums Σ_j ψ_j P* and Σ_j |P|², each frame's product placed at
    its scan position on the ``obj_shape`` canvas, come from
    ``overlap_ops.overlap_scatter`` (the Pallas kernel on a TPU, XLA's
    scatter-add elsewhere). The probe sums gather the new object's patches.
    With ``axis_name``, partial sums are psum'd across the worker axis —
    the paper's MPI_Allreduce (Fig. 9)."""
    with _phase("object_solve"):
        # object update: O = Σ ψ_j P* / Σ |P|²
        num, den = overlap_ops.overlap_scatter(psi, probe, positions,
                                               obj_shape,
                                               use_pallas=use_pallas)
        if axis_name:
            num = jax.lax.psum(num, axis_name)
            den = jax.lax.psum(den, axis_name)
        obj = num / (den + eps)

    if not update_probe:
        return obj, probe
    with _phase("probe_solve"):
        # probe update: P = Σ ψ_j O*_patch / Σ |O_patch|²
        iy, ix = _patch_indices(positions, psi.shape[-1])
        patches = obj[iy, ix]
        num_p, den_p = overlap_ops.overlap_products(psi, patches,
                                                    use_pallas=use_pallas)
        nump = jnp.sum(num_p, axis=0)
        denp = jnp.sum(den_p, axis=0)
        if axis_name:
            nump = jax.lax.psum(nump, axis_name)
            denp = jax.lax.psum(denp, axis_name)
        new_probe = nump / (denp + eps)
    return obj, new_probe


def raar_step(psi: jax.Array, mag: jax.Array, positions: jax.Array,
              probe: jax.Array, obj_shape: tuple[int, int],
              config: SolverConfig, iteration: jax.Array | int = 0,
              axis_name: str | None = None
              ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """One RAAR iteration. Returns (psi', obj, probe, fourier_error)."""
    up = config.use_pallas
    # π₁: modulus projection
    with _phase("far_field"):
        far = jnp.fft.fft2(psi)
        err = jnp.sum(jnp.square(jnp.abs(far) - mag))
        norm = jnp.sum(jnp.square(mag))
        if axis_name:
            err = jax.lax.psum(err, axis_name)
            norm = jax.lax.psum(norm, axis_name)
    with _phase("modulus"):
        far_proj = modulus_ops.modulus_project(far, mag, use_pallas=up)
    with _phase("far_field"):
        psi1 = jnp.fft.ifft2(far_proj)

    # overlap (eqs. 4-5) on the projected waves
    if isinstance(iteration, int):
        update_probe = iteration >= config.probe_update_start
    else:
        with _phase("probe_solve"):
            update_probe = (jnp.asarray(iteration)
                            >= config.probe_update_start)
    if isinstance(update_probe, bool):
        obj, new_probe = overlap_update(psi1, positions, probe, obj_shape,
                                        config.eps, axis_name,
                                        update_probe, use_pallas=up)
    else:
        obj, probe_candidate = overlap_update(psi1, positions, probe,
                                              obj_shape, config.eps,
                                              axis_name, True, use_pallas=up)
        with _phase("probe_solve"):
            new_probe = jnp.where(update_probe, probe_candidate, probe)

    # π₂π₁ψ with the refreshed (P, O)
    with _phase("exit_waves"):
        iy, ix = _patch_indices(positions, psi.shape[-1])
        p21 = new_probe[None] * obj[iy, ix]

    # RAAR combine (eq. 7); π₂ψ ≈ π₂π₁ψ under the fixed-(P,O) projector
    with _phase("combine"):
        new_psi = raar_ops.raar_combine(psi, psi1, p21, p21, config.beta,
                                        use_pallas=up)
        rel_err = jnp.sqrt(err / jnp.maximum(norm, 1e-12))
    return new_psi, obj, new_probe, rel_err


def init_waves(problem_mag: jax.Array, probe: jax.Array) -> jax.Array:
    """ψ⁰: probe modulated by random phases, scaled to measured power."""
    F, h, w = problem_mag.shape
    power = jnp.sqrt(jnp.mean(jnp.square(problem_mag), axis=(1, 2)))
    base = probe[None] * (power / (jnp.mean(jnp.abs(probe)) * h * w + 1e-9)
                          )[:, None, None]
    return base.astype(jnp.complex64)


def reconstruct(problem: PtychoProblem, config: SolverConfig
                ) -> dict[str, Any]:
    """Single-device reference reconstruction (tests, small problems)."""
    positions = jnp.asarray(problem.positions)
    probe0 = problem.probe_true * 0 + jnp.asarray(
        np.asarray(problem.probe_true) *
        np.exp(1j * 0.5 * np.random.default_rng(0).standard_normal(
            problem.probe_true.shape)).astype(np.complex64))
    psi = init_waves(problem.magnitudes, probe0)
    obj_shape = problem.object_true.shape

    @jax.jit
    def body(carry, it):
        psi, probe = carry
        psi, obj, probe, err = raar_step(psi, problem.magnitudes, positions,
                                         probe, obj_shape, config, it)
        return (psi, probe), (err, obj)

    (psi, probe), (errs, objs) = jax.lax.scan(
        body, (psi, probe0), jnp.arange(config.iterations))
    obj = objs[-1]
    return {"object": obj, "probe": probe, "errors": errs, "psi": psi}


def reconstruction_quality(obj: jax.Array, truth: jax.Array,
                           margin: int = 48) -> float:
    """Phase correlation against ground truth on the interior (global phase
    offset removed) — a scalar in [-1, 1]."""
    o = np.asarray(obj)[margin:-margin, margin:-margin]
    t = np.asarray(truth)[margin:-margin, margin:-margin]
    # remove global phase
    offset = np.angle(np.vdot(t, o))
    o = o * np.exp(-1j * offset)
    po, pt = np.angle(o), np.angle(t)
    po -= po.mean()
    pt -= pt.mean()
    denom = np.sqrt((po**2).sum() * (pt**2).sum()) + 1e-12
    return float((po * pt).sum() / denom)
