"""The whole served step's share of the chip's int8 peak, in %: two
operations per synapse of the network per stream timestep, at the
window's timestep rate."""


def read(obs):
    rate = obs.driver.steps / obs.driver.window_s
    return 100.0 * 2 * obs.net.n_synapses * rate / obs.peaks["int8_ops_per_s"]
