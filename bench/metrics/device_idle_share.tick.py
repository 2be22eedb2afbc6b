"""Share of the control fleet's traced window with no op on the device,
in %: the reader of ``device_idle_share.throughput``."""

from bench import load


def read(obs):
    return load.module(obs.root, "metrics",
                       "device_idle_share.throughput").read(obs)
