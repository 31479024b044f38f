"""Operations and bytes of the algorithms' own work, from their shapes.

These count what the algorithm needs, not what an implementation happens
to do: a pass that an implementation adds (a plane split, a copy, a second
read of a matrix) is not counted, so removing it raises the share.
"""
from __future__ import annotations

import math

C64 = 8         # bytes of a complex64 element
F32 = 4


def raar_iteration(frames: int, n: int, obj_shape: tuple[int, int]
                   ) -> tuple[float, float]:
    """One RAAR iteration over ``frames`` exit waves of ``n``²: (FLOPs,
    bytes).

    FLOPs: the forward and inverse 2-D FFT at 5·F·n²·log₂(n²) each, and per
    element of the waves 54 more: the modulus projection 8 (|Fψ|², rsqrt,
    scale of both planes) and its error sum 4; the object sums 12 (ψ·P* 6,
    |P|² 3, scatter-add 3); the probe sums 12 (ψ·O* 6, |O|² 3, sum over
    frames 3); π₂ = P·O 6; the combine 12.
    Bytes: ψ read and written, the magnitudes read, the object canvas
    written and read back once, the probe read and written, positions read.
    """
    elems = frames * n * n
    fft = 2 * 5 * elems * math.log2(n * n)
    flops = fft + 54 * elems
    canvas = obj_shape[0] * obj_shape[1]
    nbytes = (elems * (2 * C64 + F32) + 2 * canvas * C64
              + 2 * n * n * C64 + frames * 2 * F32)
    return float(flops), float(nbytes)


def least_time(flops: float, nbytes: float, peaks: dict
               ) -> tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    t_flops = flops / peaks["flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    if t_flops >= t_bytes:
        return t_flops, "compute"
    return t_bytes, "memory"
