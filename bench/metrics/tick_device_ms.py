"""Device busy time per control tick, in ms: the union of device op
intervals in the traced window over the driver's tick count."""


def read(obs):
    return obs.device_ms_per(getattr(obs.driver, "ticks", 0))
