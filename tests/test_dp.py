"""Explicit-collective DP trainer (parallel/dp.py): numerics vs the GSPMD
trainer, compression convergence — 8 virtual devices via subprocess."""
import os

from tests.test_multidevice import run_with_devices


def test_dp_step_matches_gspmd_trainer():
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.configs.base import OptimizerConfig
        from repro.parallel.dp import build_dp_train_step, init_dp_opt_state
        from repro.utils import make_mesh
        from repro.training import build_train_step, init_state

        cfg = get_config("internlm2-1.8b", reduced=True)
        opt = OptimizerConfig(lr=1e-3, warmup_steps=1, total_steps=50,
                              zero1=False, grad_clip=1.0, weight_decay=0.0)
        mesh = make_mesh((4, 2), ("data", "model"))
        key = jax.random.PRNGKey(0)
        state_ref = init_state(key, cfg, opt)
        gspmd_step = jax.jit(build_train_step(cfg, opt))

        dp_step, _ = build_dp_train_step(cfg, opt, mesh)
        params0 = state_ref["params"]
        dp_state = {"params": params0,
                    "opt": init_dp_opt_state(params0, mesh, opt)}

        batch = {"tokens": jax.random.randint(key, (8, 32), 0,
                                              cfg.vocab_size)}
        for i in range(3):
            state_ref, m_ref = gspmd_step(state_ref, batch)
            dp_state, m_dp = dp_step(dp_state, batch)
            assert abs(float(m_ref["loss"]) - float(m_dp["loss"])) < 1e-2, (
                i, float(m_ref["loss"]), float(m_dp["loss"]))
        for a, b in zip(jax.tree_util.tree_leaves(state_ref["params"]),
                        jax.tree_util.tree_leaves(dp_state["params"])):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=2e-2, atol=2e-3)
        print("OK")
    """)
    assert "OK" in out


def test_dp_compressed_training_converges():
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.configs.base import OptimizerConfig
        from repro.parallel.dp import build_dp_train_step, init_dp_opt_state
        from repro.utils import make_mesh

        cfg = get_config("internlm2-1.8b", reduced=True)
        opt = OptimizerConfig(lr=1e-3, warmup_steps=2, total_steps=40,
                              zero1=False)
        mesh = make_mesh((8,), ("data",))
        step, _ = build_dp_train_step(cfg, opt, mesh, compression="int8")
        key = jax.random.PRNGKey(0)
        from repro.models.registry import get_model
        params = get_model(cfg).init(key, cfg)
        state = {"params": params,
                 "opt": init_dp_opt_state(params, mesh, opt)}
        batch = {"tokens": jax.random.randint(key, (8, 32), 0,
                                              cfg.vocab_size)}
        losses = []
        for _ in range(12):
            state, m = step(state, batch)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0] - 0.3, losses
        assert np.isfinite(losses).all()
        print("OK", losses[0], losses[-1])
    """)
    assert "OK" in out
