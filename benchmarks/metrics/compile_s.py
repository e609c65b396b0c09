"""Seconds JAX spent lowering modules and compiling them (or reading them
from the persistent cache) before the window opened, from jax.monitoring's
duration events."""


def read(obs):
    return obs["compile"]["setup_s"]
