"""Share of the traced window with no op on the device, in %."""


def read(obs):
    return 100.0 * (1.0 - obs.busy_s / obs.window_s)
