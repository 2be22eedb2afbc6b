"""End-to-end system test: the full SoC flow (encode -> accelerate ->
decode)."""

import jax
import numpy as np

from repro.core.session import AcceleratorSession
from repro.data import mnist
from repro.snn.model import SNNModelConfig
from repro.snn.train import TrainConfig, train


def test_soc_closed_loop(rng):
    """Sensor -> encoder -> Cerebra-H -> decoder -> actuator command.

    The paper's perception-to-action loop: a trained SNN deployed through
    the session API must classify encoded sensor data above chance."""
    cfg = TrainConfig(
        model=SNNModelConfig(layer_sizes=(784, 24, 10)),
        num_steps_time=8, lr=3e-3, batch_size=64, train_steps=60)
    params, _, _ = train(
        cfg, mnist.batches("train", cfg.batch_size, cfg.train_steps, seed=7),
        log_every=0)

    from repro.snn.model import to_snnetwork
    net = to_snnetwork(params, cfg.model)
    sess = AcceleratorSession()
    sess.deploy("digits", net)
    x, y = mnist.load_or_generate("test", 128, seed=2)
    out = sess.run("digits", x, 20, jax.random.key(0))
    acc = float((np.asarray(out["predictions"]) == y).mean())
    assert acc > 0.3  # far above 10% chance through the full HW path
