"""Device busy time per pump round, in ms: the union of device op
intervals in the traced window over the harness's own round count."""


def read(obs):
    return obs.device_ms_per(getattr(obs.driver, "rounds", 0))
