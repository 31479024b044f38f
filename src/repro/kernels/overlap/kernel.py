"""Overlap-update sums (ptychography, paper eqs. 4-5), Pallas TPU kernels.

Per frame j the probe/object updates need the complex products

    num_j = ψ_j · conj(w_j)      (w = probe for the object update,
    den_j = |w_j|²                object patch for the probe update)

SHARP computes these inside CUDA kernels with atomics for the scatter.
Here two kernels split the work:

- ``overlap_scatter`` (object update): the products with the probe, placed
  at each frame's scan position and summed into the object canvas, which
  stays in VMEM for the whole call. Each frame's patch is added as an
  aligned read-add-write of whole rows, after a sublane and a lane roll put
  it in place: f32 adds in frame order, no per-pixel scatter.
- ``overlap_products`` (probe update): the products with the object
  patches, one VMEM pass over 4 input planes, 3 outputs; the sum over
  frames stays in XLA.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import dispatch


def _overlap_kernel(a_re, a_im, b_re, b_im, n_re, n_im, den):
    bre = b_re[...]
    bim = b_im[...]
    are = a_re[...]
    aim = a_im[...]
    # a · conj(b)
    n_re[...] = are * bre + aim * bim
    n_im[...] = aim * bre - are * bim
    den[...] = bre * bre + bim * bim


@functools.partial(jax.jit, static_argnames=("block_frames", "interpret"))
def overlap_products(a_re, a_im, b_re, b_im, block_frames: int = 16,
                     interpret: bool = False):
    """a, b: (F, H, W) fp32 planes -> (num_re, num_im, |b|²)."""
    F, H, W = a_re.shape
    fb = min(block_frames, F)
    grid = (-(-F // fb),)
    spec = pl.BlockSpec((fb, H, W), lambda i: (i, 0, 0))
    out_shape = [jax.ShapeDtypeStruct((F, H, W), a_re.dtype)] * 3
    return pl.pallas_call(
        _overlap_kernel,
        grid=grid,
        in_specs=[spec] * 4,
        out_specs=[spec] * 3,
        out_shape=out_shape,
        interpret=interpret,
    )(a_re, a_im, b_re, b_im)


# Past the compiler's default (``dispatch.DEFAULT_VMEM_BYTES``) this kernel
# asks for what its canvases need, up to 120 MiB, and leaves the rest to XLA.
MAX_VMEM_BYTES = 120 * 2**20
SCATTER_BLOCK = 16     # frames per grid step of ``overlap_scatter``


def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m


def _scatter_kernel(pos_ref, a_re, a_im, p_re, p_im, n_re, n_im, den,
                    s_re, s_im, s_den, *, frames: int):
    block, n, _ = a_re.shape
    rows = s_re.shape[0]
    step = pl.program_id(0)
    pre = p_re[...]
    pim = p_im[...]

    @pl.when(step == 0)
    def _():
        for ref in (n_re, n_im, den, s_re, s_im, s_den):
            ref[...] = jnp.zeros(ref.shape, ref.dtype)
        s_den[0:n, 0:n] = pre * pre + pim * pim

    def frame(j, carry):
        g = step * block + j

        @pl.when(g < frames)
        def _():
            are = a_re[j]
            aim = a_im[j]
            # a · conj(p) into the slabs' top-left n×n; the rest stays zero
            s_re[0:n, 0:n] = are * pre + aim * pim
            s_im[0:n, 0:n] = aim * pre - are * pim
            y = pos_ref[2 * g]
            x = pos_ref[2 * g + 1]
            top = pl.multiple_of(y - y % 8, 8)
            for slab, out in ((s_re, n_re), (s_im, n_im), (s_den, den)):
                placed = pltpu.roll(pltpu.roll(slab[...], y % 8, 0), x, 1)
                out[pl.ds(top, rows), :] += placed
        return carry

    jax.lax.fori_loop(0, block, frame, 0)


def scatter_vmem_bytes(frames: int, n: int, obj_shape: tuple[int, int]
                       ) -> int:
    """VMEM bytes ``overlap_scatter`` needs for ``frames`` n×n frames on an
    ``obj_shape`` canvas, an upper estimate: the three canvases,
    double-buffered frame and probe blocks, and five copies of a slab
    (three slabs, a rolled one, the rows it is added to)."""
    fb = min(SCATTER_BLOCK, frames)
    H, W = obj_shape
    canvas = (_round_up(H, 8) + 8) * _round_up(W, 128)
    tile = _round_up(n, 8) * _round_up(n, 128)
    slab = (_round_up(n, 8) + 8) * _round_up(W, 128)
    return 4 * (3 * canvas + 4 * fb * tile + 4 * tile + 5 * slab)


@functools.partial(jax.jit, static_argnames=("obj_shape", "interpret"))
def overlap_scatter(a_re, a_im, p_re, p_im, positions,
                    obj_shape: tuple[int, int], interpret: bool = False):
    """Object-update sums Σ_j place_j(a_j·conj(p)) and Σ_j place_j(|p|²).

    a_re, a_im: (F, n, n) f32 exit waves; p_re, p_im: (n, n) f32 probe, read
    once; positions: (F, 2) int32 top-left (y, x) of each frame on the
    (H, W) = ``obj_shape`` canvas. Returns (num_re, num_im, den), (H, W) f32.
    Frames go through in blocks of ``SCATTER_BLOCK``.

    Preconditions: every position lies in [0, H-n] × [0, W-n], as
    ``apps.ptycho.sim.scan_grid`` clips them; nothing checks them on the
    device, and a frame outside gives wrong sums. The three canvases,
    padded to (round8(H) + 8, round128(W)), stay in VMEM for the whole
    call, so a v5e's VMEM bounds the canvas and the frames together
    (``scatter_vmem_bytes`` up to ``MAX_VMEM_BYTES``): 64² frames fit
    objects up to 3200 × 3072. Larger shapes raise ``ValueError``;
    ``use_pallas=False`` runs XLA's scatter-add, which has no such bound.
    """
    F, n, _ = a_re.shape
    H, W = obj_shape
    fb = min(SCATTER_BLOCK, F)
    need = scatter_vmem_bytes(F, n, obj_shape)
    if need > MAX_VMEM_BYTES:
        raise ValueError(
            f"overlap_scatter: a {H}x{W} canvas with {n}x{n} frames needs "
            f"{need / 2**20:.1f} MiB of VMEM, more than the "
            f"{MAX_VMEM_BYTES >> 20} MiB it may take of a v5e's; run the "
            f"object update with use_pallas=False")
    canvas = (_round_up(H, 8) + 8, _round_up(W, 128))
    slab = (_round_up(n, 8) + 8, canvas[1])
    frame_spec = pl.BlockSpec((fb, n, n), lambda i, pos: (i, 0, 0))
    probe_spec = pl.BlockSpec((n, n), lambda i, pos: (0, 0))
    canvas_spec = pl.BlockSpec(canvas, lambda i, pos: (0, 0))
    n_re, n_im, den = pl.pallas_call(
        functools.partial(_scatter_kernel, frames=F),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(pl.cdiv(F, fb),),
            in_specs=[frame_spec, frame_spec, probe_spec, probe_spec],
            out_specs=[canvas_spec] * 3,
            scratch_shapes=[pltpu.VMEM(slab, jnp.float32)] * 3),
        out_shape=[jax.ShapeDtypeStruct(canvas, jnp.float32)] * 3,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=None if need <= dispatch.DEFAULT_VMEM_BYTES
            else need),
        interpret=interpret,
    )(positions.astype(jnp.int32).reshape(-1), a_re, a_im, p_re, p_im)
    return n_re[:H, :W], n_im[:H, :W], den[:H, :W]
