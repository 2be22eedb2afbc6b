"""Build one configuration's deployment through the program's user entry
points: ``AcceleratorSession.deploy`` then ``session.serve``."""

from __future__ import annotations

import dataclasses
import time

from bench import load, reference
from bench.reference import Network


@dataclasses.dataclass
class Deployment:
    config: dict
    net: Network
    session: object
    view: object          # repro.serving.snn.ModelStream


def network(root, config: dict, seed: int) -> Network:
    """The configuration's network, generated from ``seed`` by its
    ``networks/<kind>.py`` builder."""
    spec = config["network"]
    builder = load.module(root, "networks", spec["kind"])
    return builder.build(spec, config["neuron"], seed)


def deploy(root, config: dict, seed: int, frontend: dict | None,
           phases: dict | None = None) -> Deployment:
    """Deploy the configuration on a fresh session and serve it.

    ``frontend`` holds the async front door's parameters (queue capacity,
    backpressure, deadline) or is None for a view without one. The
    seconds each step took go into ``phases`` when it is given.
    """
    phases = {} if phases is None else phases
    t = time.perf_counter()
    from repro.core.cerebra_h import CerebraHConfig
    from repro.core.fixedpoint import FixedPointFormat
    from repro.core.mapping import ClusterGeometry
    from repro.core.network import SNNetwork
    from repro.core.session import AcceleratorSession
    from repro.serving.frontend import FrontendConfig

    net = network(root, config, seed)
    phases["program import, weights"] = time.perf_counter() - t
    fx, hw, eng = config["fixed_point"], config["hardware"], config["engine"]
    fmt = FixedPointFormat(int_bits=fx["int_bits"], frac_bits=fx["frac_bits"])
    program_net = SNNetwork(
        n_inputs=net.n_inputs, n_neurons=net.n_neurons, weights=net.weights,
        params=reference.neuron_module(root, net).program_params(
            net.neuron, fmt),
        output_slice=net.output_slice)
    accel = CerebraHConfig(geometry=ClusterGeometry(**hw["geometry"]),
                           fmt=fmt, row_mode=hw["row_mode"])
    session = AcceleratorSession(config=accel, backend=eng["backend"],
                                 fuse_steps=eng["fuse_steps"])
    t = time.perf_counter()
    session.deploy(config["name"], program_net)
    phases["deploy (place, check, quantize)"] = time.perf_counter() - t
    t = time.perf_counter()
    fe = None
    if frontend is not None:
        fe = FrontendConfig(queue_capacity=frontend["queue_capacity"],
                            backpressure=frontend["backpressure"],
                            deadline_ms=frontend["deadline_ms"])
    view = session.serve(config["name"], n_slots=eng["n_slots"],
                         chunk_steps=eng["chunk_steps"], gate=eng["gate"],
                         frontend=fe)
    phases["serve (engine, slots)"] = time.perf_counter() - t
    return Deployment(config=config, net=net, session=session, view=view)
