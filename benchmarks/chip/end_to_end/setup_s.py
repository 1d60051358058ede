"""Seconds from the process's start to the window's opening: JAX start,
data made from the seed, the store built, every shape warmed (compiled,
or loaded from the compile cache)."""


def read(run):
    return run.setup_s
