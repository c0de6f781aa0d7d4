"""Executor: step programs compiled during the window (should be 0)."""


def read(obs):
    return obs.compiles
