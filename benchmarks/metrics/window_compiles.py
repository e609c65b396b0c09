"""Modules that reached XLA inside the window (jax.monitoring). Each is a
compilation or a read from the persistent cache that the window paid for."""


def read(obs):
    return len(obs["compile"]["window_modules"])
