"""The spiking P-controller of Stagsted et al. (RSS 2020) as SNAP-V's
robot-control example wires it, written here from its description.

Two inputs, error+ (setpoint above the state) and error- (below), and
three populations of ``per_population`` neurons: E+ (neurons
``0..n-1``), E- (``n..2n-1``) and an inhibition pool (``2n..3n-1``).
error+ excites every E+ neuron and error- every E- neuron with
``gain``; E+ neuron ``i`` excites pool neuron ``i`` with ``relay``, and
pool neuron ``i`` inhibits E- neuron ``i`` with ``inhibition``. The
outputs are E+ then E-; the actuator command is
``u_max * (rate(E+) - rate(E-))``. The weights are the example's
hand-wired values (Stagsted et al. publish the construction, not these
numbers), so the seed does not change them.
"""

import numpy as np

from bench.reference import Network


def build(spec: dict, neuron: dict, seed: int) -> Network:
    n = int(spec["per_population"])
    w = np.zeros((2 + 3 * n, 3 * n), np.float32)
    i = np.arange(n)
    w[0, i] = spec["gain"]
    w[1, n + i] = spec["gain"]
    w[2 + i, 2 * n + i] = spec["relay"]
    w[2 + 2 * n + i, n + i] = spec["inhibition"]
    return Network(weights=w, n_inputs=2, n_neurons=3 * n,
                   output_slice=(0, 2 * n), neuron=neuron)
