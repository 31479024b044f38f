"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps.ptycho.solver import overlap_update
from repro.kernels import dispatch
from repro.kernels.art import ops as art_ops
from repro.kernels.art import ref as art_ref
from repro.kernels.flash_attention import kernel as fa_kernel
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention import ref as fa_ref
from repro.kernels.modulus import kernel as mod_kernel
from repro.kernels.modulus import ref as mod_ref
from repro.kernels.overlap import kernel as ov_kernel
from repro.kernels.overlap import ref as ov_ref
from repro.kernels.raar import kernel as raar_kernel
from repro.kernels.raar import ref as raar_ref


def _planes(key, shape, dtype=jnp.float32, n=1):
    keys = jax.random.split(key, n)
    return [jax.random.normal(k, shape, dtype) for k in keys]


def test_dispatch_never_interprets_on_tpu(monkeypatch):
    """Off the TPU: oracle by default, interpret mode when a test forces the
    kernel. On a TPU: the kernel by default, and always compiled."""
    assert dispatch.kernel_mode() == (False, True)
    assert dispatch.kernel_mode(True) == (True, True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert dispatch.kernel_mode() == (True, False)
    assert dispatch.kernel_mode(True) == (True, False)
    assert dispatch.kernel_mode(False) == (False, False)


# -- modulus -------------------------------------------------------------------
@pytest.mark.parametrize("shape", [(4, 16, 16), (7, 32, 32), (16, 8, 24),
                                   (1, 64, 64)])
@pytest.mark.parametrize("fb", [2, 16])
def test_modulus_sweep(shape, fb):
    key = jax.random.PRNGKey(hash(shape) % 2**31)
    re, im, mag = _planes(key, shape, n=3)
    mag = jnp.abs(mag)
    got = mod_kernel.modulus_project(re, im, mag, block_frames=fb,
                                     interpret=True)
    want = mod_ref.modulus_project_ref(re, im, mag)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-6, atol=1e-6)


def test_modulus_projection_property():
    """|π₁ψ| == measured magnitude (the modulus constraint, paper eq. 1)."""
    key = jax.random.PRNGKey(0)
    re, im, mag = _planes(key, (3, 16, 16), n=3)
    mag = jnp.abs(mag) + 0.1
    ore, oim = mod_kernel.modulus_project(re, im, mag, interpret=True)
    np.testing.assert_allclose(np.sqrt(np.asarray(ore)**2 + np.asarray(oim)**2),
                               np.asarray(mag), rtol=1e-4, atol=1e-4)


# -- raar ----------------------------------------------------------------------
@pytest.mark.parametrize("shape", [(4, 16, 16), (5, 8, 40)])
@pytest.mark.parametrize("beta", [0.5, 0.75, 0.9])
def test_raar_sweep(shape, beta):
    key = jax.random.PRNGKey(1)
    planes = _planes(key, shape, n=8)
    got = raar_kernel.raar_combine(*planes, beta=beta, block_frames=3,
                                   interpret=True)
    want = raar_ref.raar_combine_ref(*planes, beta=beta)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-6, atol=1e-6)


# -- overlap -------------------------------------------------------------------
@pytest.mark.parametrize("shape", [(4, 16, 16), (9, 24, 8)])
def test_overlap_sweep(shape):
    key = jax.random.PRNGKey(2)
    a_re, a_im, b_re, b_im = _planes(key, shape, n=4)
    got = ov_kernel.overlap_products(a_re, a_im, b_re, b_im, block_frames=4,
                                     interpret=True)
    want = ov_ref.overlap_products_ref(a_re, a_im, b_re, b_im)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-6, atol=1e-6)


def test_overlap_matches_complex_ref():
    key = jax.random.PRNGKey(3)
    a_re, a_im, b_re, b_im = _planes(key, (3, 8, 8), n=4)
    a = a_re + 1j * a_im
    b = b_re + 1j * b_im
    n_re, n_im, den = ov_kernel.overlap_products(a_re, a_im, b_re, b_im,
                                                 interpret=True)
    num_c, den_c = ov_ref.overlap_products_complex(a, b)
    np.testing.assert_allclose(np.asarray(n_re + 1j * n_im),
                               np.asarray(num_c), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(den), np.asarray(den_c),
                               rtol=1e-5, atol=1e-5)


def _scatter_case(frames, n, obj, seed):
    """Waves, probe and positions for ``overlap_scatter``: random positions
    on the canvas plus the corners 0 and H-n, a row off the 8-row tiling
    and a patch across the 128-lane boundary."""
    rng = np.random.default_rng(seed)
    a_re, a_im = rng.standard_normal((2, frames, n, n), np.float32)
    p_re, p_im = rng.standard_normal((2, n, n), np.float32)
    lim = obj - n
    pos = rng.integers(0, lim + 1, (frames, 2))
    edges = [(0, 0), (lim, lim), (lim, 0), (min(5, lim), min(120, lim))]
    pos[:len(edges)] = edges[:frames]
    return a_re, a_im, p_re, p_im, pos.astype(np.int32)


def _scatter_oracle(a_re, a_im, p_re, p_im, pos, obj):
    """float64 ``np.add.at`` of ψ·conj(P) and |P|² at each position."""
    n = a_re.shape[-1]
    p = p_re.astype(np.float64) + 1j * p_im
    num_o = (a_re.astype(np.float64) + 1j * a_im) * np.conj(p)[None]
    den_o = np.broadcast_to(np.abs(p) ** 2, num_o.shape)
    iy = pos[:, 0, None, None] + np.arange(n)[None, :, None]
    ix = pos[:, 1, None, None] + np.arange(n)[None, None, :]
    num = np.zeros((obj, obj), np.complex128)
    den = np.zeros((obj, obj))
    np.add.at(num, (iy, ix), num_o)
    np.add.at(den, (iy, ix), den_o)
    return num.real, num.imag, den


@pytest.mark.parametrize("frames,n,obj", [
    (512, 64, 256),     # paper Table II
    (16, 16, 48),       # the small size of the step tests
    (20, 24, 64),       # 16-frame blocks: the second holds 4
    (3, 8, 200),        # fewer frames than a block; canvas past 128 lanes
])
def test_overlap_scatter_sweep(frames, n, obj):
    case = _scatter_case(frames, n, obj, seed=frames)
    got = ov_kernel.overlap_scatter(*map(jnp.asarray, case),
                                    obj_shape=(obj, obj), interpret=True)
    for g, w in zip(got, _scatter_oracle(*case, obj)):
        assert g.shape == (obj, obj)
        np.testing.assert_allclose(np.asarray(g), w, rtol=1e-5,
                                   atol=1e-6 * np.abs(w).max())


def test_overlap_update_pallas_matches_xla_scatter():
    """``overlap_update``'s object and probe with the Pallas scatter
    (interpret) against XLA's ``.at[].add`` path."""
    a_re, a_im, p_re, p_im, pos = _scatter_case(40, 16, 72, seed=7)
    psi = jnp.asarray(a_re + 1j * a_im)
    probe = jnp.asarray(p_re + 1j * p_im)
    want = overlap_update(psi, jnp.asarray(pos), probe, (72, 72),
                          use_pallas=False)
    got = overlap_update(psi, jnp.asarray(pos), probe, (72, 72),
                         use_pallas=True)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-5)


# -- art ----------------------------------------------------------------------
def _art_system(nrow, ncol, k):
    """A random system, the measurements of ``k`` random images through it,
    and ``1/‖A_j‖²``."""
    A = jax.random.normal(jax.random.PRNGKey(4), (nrow, ncol))
    f_true = jax.random.normal(jax.random.PRNGKey(5), (k, ncol))
    return A, f_true @ A.T, 1.0 / jnp.sum(A * A, axis=1)


@pytest.mark.parametrize("nrow,ncol", [(8, 16), (20, 12), (32, 64),
                                       (13, 128)])
@pytest.mark.parametrize("iters", [1, 3])
@pytest.mark.parametrize("k", [1, 3, 16, 20])
def test_art_sweep(nrow, ncol, iters, k):
    """Rows stream in blocks of 8; 20 and 13 rows pin the padding. Slices
    are swept together in blocks of up to 16: one slice and 3 pad the
    block to 8, 16 fill one block, 20 add a part-filled second block. Each
    slice matches the reference run on it alone."""
    from repro.kernels.art import kernel as art_kernel
    A, B, inv_rip = _art_system(nrow, ncol, k)
    got = art_kernel.art_sweep(A, B, inv_rip, beta=1.0, iters=iters,
                               interpret=True)
    assert got.shape == (k, ncol)
    alone = jax.jit(lambda b: art_ref.art_sweep_ref(
        A, b, inv_rip, jnp.zeros((ncol,)), beta=1.0, iters=iters))
    for b, g in zip(B, got):
        want = alone(b)
        np.testing.assert_allclose(np.asarray(g), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


def test_art_sweep_slice_ignores_its_block_mates():
    """A slice's image is the same, to the bit, alone or in a block with 15
    or 19 other slices: the slices share rows of ``A`` but no arithmetic."""
    from repro.kernels.art import kernel as art_kernel
    A, B, inv_rip = _art_system(20, 256, 20)
    alone = art_kernel.art_sweep(A, B[3:4], inv_rip, iters=2, interpret=True)
    for block in (B[:16], B, B[::-1]):
        got = art_kernel.art_sweep(A, block, inv_rip, iters=2,
                                   interpret=True)
        row = [i for i, b in enumerate(block) if bool(jnp.all(b == B[3]))]
        np.testing.assert_array_equal(np.asarray(got[row[0]]),
                                      np.asarray(alone[0]))


def test_art_converges_consistent_system():
    """Kaczmarz converges on a consistent overdetermined system."""
    key = jax.random.PRNGKey(6)
    A = jax.random.normal(key, (64, 16))
    f_true = jax.random.normal(jax.random.PRNGKey(7), (16,))
    b = A @ f_true
    [f] = art_ops.art_sweep_slices(A, b[None], art_ops.inverse_row_norms(A),
                                   beta=1.0, iters=30, use_pallas=True)
    np.testing.assert_allclose(np.asarray(f), np.asarray(f_true),
                               rtol=1e-3, atol=1e-3)


# -- flash attention ------------------------------------------------------------
@pytest.mark.parametrize("S,hd,bq,bkv", [(64, 16, 16, 32), (128, 32, 32, 32),
                                         (32, 8, 32, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(S, hd, bq, bkv, dtype):
    key = jax.random.PRNGKey(8)
    BH = 4
    q = jax.random.normal(key, (BH, S, hd), dtype)
    k = jax.random.normal(jax.random.PRNGKey(9), (BH, S, hd), dtype)
    v = jax.random.normal(jax.random.PRNGKey(10), (BH, S, hd), dtype)
    got = fa_kernel.flash_attention_bhsd(q, k, v, block_q=bq, block_kv=bkv,
                                         causal=True, interpret=True)
    want = fa_ref.attention_ref(q, k, v, causal=True)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_model_layout():
    """ops wrapper: (B, S, H, hd) layout, padding path."""
    key = jax.random.PRNGKey(11)
    B, S, H, hd = 2, 40, 4, 16       # S=40 not divisible by blocks -> pad
    q = jax.random.normal(key, (B, S, H, hd))
    k = jax.random.normal(jax.random.PRNGKey(12), (B, S, H, hd))
    v = jax.random.normal(jax.random.PRNGKey(13), (B, S, H, hd))
    got = fa_ops.flash_attention(q, k, v, block_q=16, block_kv=16,
                                 use_pallas=True)
    from repro.models.attention import naive_attention
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    want = naive_attention(q, k, v, pos, pos, causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-5, atol=2e-5)
