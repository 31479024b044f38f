"""Compile the main-path Pallas kernels for a described TPU v5e, chip-free.

Interpret mode cannot see what the TPU's compiler refuses (block shapes off
the (8, 128) tiling, too much VMEM), so every main-path kernel is compiled
here at the paper's widths — 512 frames of 64² on a 256² object for
ptychography, the default 64-ray, 25-angle ART system for tomography — for
one chip of a described ``v5e:2x2``. The topology is described inside a
fixture, never at import: only one process may load the TPU library, and
every test worker imports this file.
"""
import contextlib
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.apps.ptycho import solver
from repro.apps.ptycho.solver import SolverConfig, raar_step
from repro.kernels import dispatch
from repro.kernels.art import kernel as art_kernel
from repro.kernels.modulus import kernel as mod_kernel
from repro.kernels.overlap import kernel as ov_kernel
from repro.kernels.raar import kernel as raar_kernel

FRAMES, FRAME = 512, 64          # paper Table II
OBJ = 256
NRAY, ANGLES, SLICES = 64, 25, 32  # examples/tomo_pipeline.py defaults
CELL_NRAY, CELL_SLICES = 256, 16   # tomo-art256: 16 slices per partition


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # any failure: no TPU library here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a chip-less compile can be written to the persistent cache but never
    # read back; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _pallas_grids(jaxpr) -> list[tuple[int, ...]]:
    """The grid of each ``pallas_call`` in ``jaxpr``, nested jaxprs too."""
    grids = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            grids.append(eqn.params["grid_mapping"].grid)
        for p in eqn.params.values():
            inner = getattr(p, "jaxpr", None)
            if inner is not None:
                grids += _pallas_grids(getattr(inner, "jaxpr", inner))
    return grids


def _assert_kernel(compiled, n: int = 1) -> None:
    got = compiled.as_text().count("tpu_custom_call")
    assert got >= n, f"{got} tpu_custom_call(s) in the compiled program"


@pytest.mark.parametrize("name,fn,n_in", [
    ("modulus", mod_kernel.modulus_project, 3),
    ("overlap", ov_kernel.overlap_products, 4),
    ("raar", raar_kernel.raar_combine, 8),
])
def test_elementwise_kernel_compiles(one_chip, name, fn, n_in):
    x = _spec((FRAMES, FRAME, FRAME), one_chip)
    _assert_kernel(fn.lower(*[x] * n_in).compile())


@pytest.mark.parametrize("nray,nrow,slices", [
    pytest.param(NRAY, ANGLES * NRAY, SLICES, id=str(ANGLES * NRAY)),
    pytest.param(NRAY, ANGLES * NRAY + 3, SLICES, id=str(ANGLES * NRAY + 3)),
    # the tomo-art256 cell: 6400 x 65536 (1.68 GB), one partition's 16 slices
    pytest.param(CELL_NRAY, ANGLES * CELL_NRAY, CELL_SLICES,
                 id=str(ANGLES * CELL_NRAY)),
    # 512 rays: the (16, 262144) images pass the compiler's default scoped
    # VMEM, so the kernel asks for what it needs
    pytest.param(2 * CELL_NRAY, ANGLES * 2 * CELL_NRAY, CELL_SLICES,
                 id=str(ANGLES * 2 * CELL_NRAY)),
])
def test_art_sweep_compiles_batched(one_chip, nray, nrow, slices):
    """(8, Ncol) row blocks, the slices swept together as the tomography
    solver runs them; the second case pads its rows to a multiple of 8. At
    256 rays a row block is (8, 65536) and the resident images (16, 65536):
    the case pins the kernel's VMEM fit, and the matrix stays one operand,
    not one copy per slice; at 512 rays the images need more VMEM than the
    compiler's default. The grid makes ``matrix_passes`` passes of
    ``A``: one per sweep for the cell's 16 slices, two for the example's
    32."""
    ncol, sweeps = nray * nray, 2

    def sweep(A, B, inv_rip):
        return art_kernel.art_sweep(A, B, inv_rip, beta=1.0, iters=sweeps)

    args = (_spec((nrow, ncol), one_chip), _spec((slices, nrow), one_chip),
            _spec((nrow,), one_chip))
    compiled = jax.jit(sweep).lower(*args).compile()
    _assert_kernel(compiled)
    # no copy of A beyond the argument itself: the temporaries stay under
    # one more A
    assert compiled.memory_analysis().temp_size_in_bytes < nrow * ncol
    [grid] = _pallas_grids(jax.make_jaxpr(sweep)(*args).jaxpr)
    passes = art_kernel.matrix_passes(slices, sweeps)
    assert grid[0] * grid[1] == passes == sweeps * -(-slices // 16)


def test_raar_step_compiles_with_pallas_forced(one_chip, monkeypatch):
    """One whole RAAR iteration with all four kernel calls compiled: the
    described chip is not the default backend, so force the dispatch."""
    monkeypatch.setattr(dispatch, "kernel_mode",
                        lambda use_pallas=None: (True, False))
    cfg = SolverConfig()

    def step(psi, mag, pos, probe):
        return raar_step(psi, mag, pos, probe, (OBJ, OBJ), cfg, 5)

    c64 = jnp.complex64
    compiled = jax.jit(step).lower(
        _spec((FRAMES, FRAME, FRAME), one_chip, c64),
        _spec((FRAMES, FRAME, FRAME), one_chip),
        _spec((FRAMES, 2), one_chip, jnp.int32),
        _spec((FRAME, FRAME), one_chip, c64)).compile()
    _assert_kernel(compiled, n=4)   # modulus, overlap scatter and
    #                                 products, combine
    peak = compiled.memory_analysis().peak_memory_in_bytes
    assert 0 < peak < 8 * 2**30, peak        # half of a v5e's 16 GB HBM


def test_object_solve_runs_the_overlap_scatter_kernel(one_chip, monkeypatch):
    """The Table II step, kernels forced, sums the object side in the
    ``overlap_scatter`` kernel under ``raar/object_solve``, and XLA's
    per-pixel scatter is gone from the program: no ``scatter`` instruction
    is left in any phase, fusions included (the TPU compiler's scatter
    rewrite drops an op's name, so the phase alone would not find one)."""
    monkeypatch.setattr(dispatch, "kernel_mode",
                        lambda use_pallas=None: (True, False))
    cfg = SolverConfig()
    c64 = jnp.complex64
    text = jax.jit(lambda psi, mag, pos, probe, it: raar_step(
        psi, mag, pos, probe, (OBJ, OBJ), cfg, it)).lower(
        _spec((FRAMES, FRAME, FRAME), one_chip, c64),
        _spec((FRAMES, FRAME, FRAME), one_chip),
        _spec((FRAMES, 2), one_chip, jnp.int32),
        _spec((FRAME, FRAME), one_chip, c64),
        _spec((), one_chip, jnp.int32)).compile().as_text()
    calls = re.findall(r"%overlap_scatter[.\d]* = .*custom-call\(.*"
                       r"op_name=\"[^\"]*/raar/(\w+)/", text)
    assert calls == ["object_solve"], calls
    assert not re.findall(r".*\bscatter\(.*", text)


@pytest.mark.parametrize("frames,n,obj,fits", [
    (FRAMES, FRAME, (3200, 3072), True),    # the largest canvas for 64²
    (64, 256, (1024, 1024), True),          # large frames, a raised limit
    (FRAMES, FRAME, (3264, 3072), False),   # one step past the largest
    (256, 128, (1024, 8192), False),
])
def test_overlap_scatter_vmem_ceiling(one_chip, frames, n, obj, fits):
    """The scatter kernel compiles for a v5e up to the VMEM its estimate
    allows, and past it refuses with ``ValueError`` before compiling."""
    wave = _spec((frames, n, n), one_chip)
    probe = _spec((n, n), one_chip)
    args = (wave, wave, probe, probe, _spec((frames, 2), one_chip, jnp.int32))
    need = ov_kernel.scatter_vmem_bytes(frames, n, obj)
    assert (need <= ov_kernel.MAX_VMEM_BYTES) == fits, need
    if fits:
        _assert_kernel(ov_kernel.overlap_scatter.lower(
            *args, obj_shape=obj).compile())
    else:
        with pytest.raises(ValueError, match="use_pallas=False"):
            ov_kernel.overlap_scatter.lower(*args, obj_shape=obj)


def test_raar_phase_scopes_leave_the_tpu_program_unchanged(one_chip,
                                                           monkeypatch):
    """The RAAR step's phase scopes are metadata in the program the chip
    runs too: with the kernels compiled and the iteration traced, the
    program without them has the same ops, operands and fusions, and each
    kernel call sits in the phase that owns it."""
    monkeypatch.setattr(dispatch, "kernel_mode",
                        lambda use_pallas=None: (True, False))
    cfg = SolverConfig()
    c64 = jnp.complex64
    args = (_spec((FRAMES, FRAME, FRAME), one_chip, c64),
            _spec((FRAMES, FRAME, FRAME), one_chip),
            _spec((FRAMES, 2), one_chip, jnp.int32),
            _spec((FRAME, FRAME), one_chip, c64),
            _spec((), one_chip, jnp.int32))

    def compiled_text():
        step = jax.jit(lambda psi, mag, pos, probe, it: raar_step(
            psi, mag, pos, probe, (OBJ, OBJ), cfg, it))
        return step.lower(*args).compile().as_text()

    def body(text):
        text = text[re.search(r"^(%|ENTRY)", text, re.M).start():]
        return re.sub(r", metadata=\{[^}]*\}", "", text)

    scoped = compiled_text()
    kernels = re.findall(r"%(modulus_project|overlap_\w+|raar_combine)"
                         r"[.\d]* = .*op_name=\"[^\"]*/raar/(\w+)/", scoped)
    assert sorted(kernels) == [("modulus_project", "modulus"),
                               ("overlap_products", "probe_solve"),
                               ("overlap_scatter", "object_solve"),
                               ("raar_combine", "combine")]
    monkeypatch.setattr(solver, "_phase",
                        lambda name: contextlib.nullcontext())
    assert body(compiled_text()) == body(scoped)
