"""Pallas TPU kernels for the compute hot spots the paper optimizes (SHARP's
GPU kernels -> TPU): ptycho modulus projection, RAAR combine, overlap
products, tomography ART row sweep, and flash attention for the LM serving
path. Each kernel ships kernel.py (pl.pallas_call + BlockSpec), ops.py
(wrapper that picks kernel or oracle through the one rule in dispatch.py)
and ref.py (pure-jnp oracle); tests sweep shapes/dtypes against the oracle
in interpret mode, and tests/test_tpu_compile.py compiles the main-path
kernels for a described TPU v5e."""
