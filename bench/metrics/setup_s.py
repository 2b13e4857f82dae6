"""Seconds from process start to the first measured step or request:
loading, weights, warm-up and, in a run that compiles, compilation."""


def read(obs):
    return obs.get("setup_s")
