"""XLA programs compiled or loaded inside the measured window
(``jax.monitoring``); every shape is warmed up in set-up, so this reads 0."""


def read(run):
    return run.compiles_in_window
