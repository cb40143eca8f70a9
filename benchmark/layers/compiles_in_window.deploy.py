"""compiles_in_window.deploy

Backend compile events (JAX monitoring) inside the window; must read 0.
"""
from benchmark.layers import _lib


def read(obs):
    return _lib.compiles_in_window(obs)
