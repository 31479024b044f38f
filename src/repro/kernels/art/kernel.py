"""ART row-action sweep (tomography, paper Fig. 12), Pallas TPU kernel.

Kaczmarz/ART is inherently sequential over rays:

    for each ray j:   f += β · (b_j - ⟨A_j, f⟩) / ‖A_j‖² · A_j

TomViz runs this as a Python/NumPy loop; SHARP-era GPUs would need global
synchronization per row. The TPU-idiomatic port sweeps a block of slices
that share one system matrix together: their images lie on the sublanes of
one (k_blk, Ncol) output block whose index map is constant over the
sequential grid steps (iters, rows / 8), so Pallas keeps it resident in
VMEM while the rows of ``A`` stream HBM→VMEM in (8, Ncol) blocks, the TPU's
sublane tile. So each row is read from HBM once per sweep for the whole
block, not once per slice. Inside a step the 8 rows run in order; for each
row the kernel takes the k dots ⟨A_j, f_s⟩, the k residuals, and adds
β·resid_s·A_j to each image: per slice the row-at-a-time Kaczmarz of
``ref.py``, in f32 on the VPU (only the lane-sum order of a dot differs).
The images are walked in column chunks of up to ``CHUNK`` lanes, and the
pass that applies row r's update also accumulates row r+1's dots from the
updated images, so a row costs one read and one write of the images.

Layouts: ``b`` travels as (Nrow/8, k_pad, 8) in (k_blk, 8) blocks and
``1/‖A_j‖²`` as (Nrow/8, 1, 8): the lane dimension is the array's full last
dimension, which the TPU's (8, 128) tiling allows. Rows are padded to a
multiple of 8 with ``inv_rip = 0`` and slices to a multiple of k_blk with
``b = 0``: a padded row changes no image, and a padded slice's image stays
0. Past ``SLICE_BLOCK`` slices a leading grid axis runs over slice blocks,
each with its own pass of ``A`` per sweep.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import dispatch

ROWS = 8            # rows per grid step: the f32 sublane tile
SLICE_BLOCK = 16    # most slices per pass of A (a tomography partition's)
CHUNK = 1024        # most lanes of the images one loop step touches


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def slice_block(k: int) -> int:
    """Slices swept together in one pass of ``A``: ``k`` rounded up to the
    sublane tile, at most ``SLICE_BLOCK``."""
    return min(_round_up(k, ROWS), SLICE_BLOCK)


def matrix_passes(k: int, iters: int) -> int:
    """Passes of ``A`` that ``art_sweep`` makes for ``k`` slices:
    ``iters × ⌈k / k_blk⌉``."""
    return iters * -(-k // slice_block(k))


def _chunk(ncol: int) -> int:
    """Lanes per column chunk: the largest multiple of 128 up to ``CHUNK``
    that divides ``ncol``; the whole row where there is none."""
    for c in range(CHUNK, 0, -128):
        if ncol % c == 0:
            return c
    return ncol


def sweep_vmem_bytes(k_blk: int, ncol: int) -> int:
    """VMEM bytes the kernel needs, an upper estimate: the double-buffered
    image block and row block, and four chunk-sized temporaries."""
    return 4 * (2 * k_blk * ncol + 2 * ROWS * ncol
                + 4 * k_blk * _chunk(ncol)) + (1 << 20)


def _make_kernel(beta: float, k_blk: int, ncol: int):
    chunk = _chunk(ncol)
    nchunk = ncol // chunk

    def kernel(a_ref, b_ref, rip_ref, f_ref):
        it = pl.program_id(1)
        j = pl.program_id(2)

        @pl.when(jnp.logical_and(it == 0, j == 0))
        def _():
            f_ref[...] = jnp.zeros_like(f_ref)

        def over_columns(step):
            """``step(cols, acc)`` over the column chunks; the per-slice
            lane sums of what it accumulates, (k_blk, 1)."""
            acc = jnp.zeros((k_blk, chunk), jnp.float32)
            if nchunk == 1:
                acc = step(slice(None), acc)
            else:
                acc = jax.lax.fori_loop(0, nchunk, lambda c, acc: step(
                    pl.ds(pl.multiple_of(c * chunk, chunk), chunk), acc), acc)
            return jnp.sum(acc, axis=1, keepdims=True)

        def apply(r: int, coef, nxt: int | None):
            """f += coef ⊗ A_r, and the dots of row ``nxt`` with the new f."""
            def step(cols, acc):
                f = f_ref[:, cols] + coef * a_ref[r:r + 1, cols]
                f_ref[:, cols] = f
                if nxt is None:
                    return acc
                return acc + f * a_ref[nxt:nxt + 1, cols]
            return over_columns(step)

        dots = over_columns(
            lambda cols, acc: acc + f_ref[:, cols] * a_ref[0:1, cols])
        for r in range(ROWS):                       # in order: Kaczmarz
            resid = (b_ref[:, r:r + 1] - dots) * rip_ref[:, r:r + 1]
            coef = jnp.broadcast_to(beta * resid, (k_blk, chunk))
            dots = apply(r, coef, r + 1 if r + 1 < ROWS else None)

    return kernel


@functools.partial(jax.jit, static_argnames=("beta", "iters", "interpret"))
def art_sweep(A: jax.Array, B: jax.Array, inv_rip: jax.Array,
              beta: float = 1.0, iters: int = 1,
              interpret: bool = False) -> jax.Array:
    """A: (Nrow, Ncol) fp32, shared by every slice; B: (k, Nrow), one
    slice's measurements per row; inv_rip: (Nrow,) = 1/‖A_j‖². Returns the
    k images, (k, Ncol), after ``iters`` full sweeps from ``f = 0``."""
    nrow, ncol = A.shape
    k = B.shape[0]
    k_blk = slice_block(k)
    k_pad = _round_up(k, k_blk)
    pad = (-nrow) % ROWS
    if pad:
        A = jnp.pad(A, ((0, pad), (0, 0)))
        inv_rip = jnp.pad(inv_rip, (0, pad))     # 0: padded rows are no-ops
    steps = (nrow + pad) // ROWS
    B = jnp.pad(B.astype(jnp.float32), ((0, k_pad - k), (0, pad)))
    need = sweep_vmem_bytes(k_blk, ncol)
    F = pl.pallas_call(
        _make_kernel(beta, k_blk, ncol),
        grid=(k_pad // k_blk, iters, steps),
        in_specs=[
            pl.BlockSpec((ROWS, ncol), lambda s, i, j: (j, 0)),  # row stream
            pl.BlockSpec((None, k_blk, ROWS), lambda s, i, j: (j, s, 0)),
            pl.BlockSpec((None, 1, ROWS), lambda s, i, j: (j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((k_blk, ncol), lambda s, i, j: (s, 0)),
        out_shape=jax.ShapeDtypeStruct((k_pad, ncol), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=None if need <= dispatch.DEFAULT_VMEM_BYTES
            else need),
        interpret=interpret,
        name="art_sweep",          # the kernel's op name in a device trace
    )(A, B.reshape(k_pad, steps, ROWS).transpose(1, 0, 2),
      inv_rip.astype(jnp.float32).reshape(steps, 1, ROWS))
    return F[:k]
