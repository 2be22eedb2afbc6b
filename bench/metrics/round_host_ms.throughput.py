"""Host time per pump round, in ms: the wall time of each ``bench.pump``
span less the device busy time inside it, averaged over the window."""


def read(obs):
    return obs.host_ms_per_span("bench.pump")
