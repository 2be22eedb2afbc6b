"""Host time per control tick, in ms: the wall time of each
``bench.feed`` span (one tick's ``feed_many`` and its decoded commands)
less the device busy time inside it, averaged over the window."""


def read(obs):
    return obs.host_ms_per_span("bench.feed")
