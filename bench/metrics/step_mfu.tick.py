"""The control fleet's whole served step as a share of the chip's int8
peak, in %: the reader of ``step_mfu`` (two operations per synapse per
stream timestep, at the window's timestep rate)."""

from bench import load


def read(obs):
    return load.module(obs.root, "metrics", "step_mfu").read(obs)
