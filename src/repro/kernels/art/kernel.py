"""ART row-action sweep (tomography, paper Fig. 12), Pallas TPU kernel.

Kaczmarz/ART is inherently sequential over rays:

    for each ray j:   f += β · (b_j - ⟨A_j, f⟩) / ‖A_j‖² · A_j

TomViz runs this as a Python/NumPy loop; SHARP-era GPUs would need global
synchronization per row. The TPU-idiomatic port: the image f lives in VMEM
as a (1, Ncol) output block with a CONSTANT index map — Pallas keeps it
resident across sequential grid steps (grid = (iters, rows / 8)) while the
rows of the system matrix stream HBM→VMEM in (8, Ncol) blocks, the TPU's
sublane tile. Inside a step the 8 rows run in order, so the Kaczmarz order
(and the result) is the row-at-a-time one of ``ref.py``. ``b`` and
``1/‖A_j‖²`` travel as (Nrow, 1) columns in (8, 1) blocks — a layout the TPU
tiling allows, also when ``vmap`` over slices adds a batch axis, where 1-D
or SMEM blocks are refused. Rows are padded to a multiple of 8 with
``inv_rip = 0``, which makes a padded row a no-op.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROWS = 8        # rows per grid step: the f32 sublane tile


def _make_kernel(beta: float):
    def kernel(a_ref, b_ref, rip_ref, f0_ref, f_ref):
        it = pl.program_id(0)
        j = pl.program_id(1)

        @pl.when(jnp.logical_and(it == 0, j == 0))
        def _():
            f_ref[...] = f0_ref[...]

        f = f_ref[...]
        for r in range(ROWS):                       # in order: Kaczmarz
            row = a_ref[r:r + 1, :]
            dot = jnp.sum(row * f, axis=1, keepdims=True)      # (1, 1)
            resid = (b_ref[r:r + 1, :] - dot) * rip_ref[r:r + 1, :]
            f = f + beta * resid * row
        f_ref[...] = f

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("beta", "iters", "interpret"))
def art_sweep(A: jax.Array, b: jax.Array, inv_rip: jax.Array,
              f0: jax.Array, beta: float = 1.0, iters: int = 1,
              interpret: bool = False) -> jax.Array:
    """A: (Nrow, Ncol) fp32; b: (Nrow,); inv_rip: (Nrow,) = 1/‖A_j‖²;
    f0: (Ncol,) initial image. Returns f after ``iters`` full sweeps."""
    nrow, ncol = A.shape
    pad = (-nrow) % ROWS
    if pad:
        A = jnp.pad(A, ((0, pad), (0, 0)))
        b = jnp.pad(b, (0, pad))
        inv_rip = jnp.pad(inv_rip, (0, pad))     # 0: padded rows are no-ops
    col = pl.BlockSpec((ROWS, 1), lambda i, j: (j, 0))
    f = pl.pallas_call(
        _make_kernel(beta),
        grid=(iters, (nrow + pad) // ROWS),
        in_specs=[
            pl.BlockSpec((ROWS, ncol), lambda i, j: (j, 0)),  # row stream
            col,
            col,
            pl.BlockSpec((1, ncol), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, ncol), lambda i, j: (0, 0)),  # resident
        out_shape=jax.ShapeDtypeStruct((1, ncol), jnp.float32),
        interpret=interpret,
        name="art_sweep",          # the kernel's op name in a device trace
    )(A, b.reshape(-1, 1).astype(jnp.float32),
      inv_rip.reshape(-1, 1).astype(jnp.float32),
      f0.reshape(1, ncol).astype(jnp.float32))
    return f.reshape(ncol)
