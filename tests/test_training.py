"""Training substrate: optimizers, schedules, straggler detection."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.distributed.straggler import StragglerDetector, rebalance_shards
from repro.training import optimizers


# --------------------------------------------------------------------------
# optimizers
# --------------------------------------------------------------------------
@pytest.mark.parametrize("make", [
    lambda: optimizers.sgd(0.1, momentum=0.9),
    lambda: optimizers.adam(0.1),
    lambda: optimizers.adamw(0.1, weight_decay=0.0),
])
def test_optimizers_minimize_quadratic(make):
    opt = make()
    params = {"w": jnp.asarray([3.0, -2.0]), "b": jnp.asarray(5.0)}
    state = opt.init(params)
    loss = lambda p: jnp.sum(p["w"] ** 2) + p["b"] ** 2
    for _ in range(200):
        grads = jax.grad(loss)(params)
        updates, state = opt.update(grads, state, params)
        params = optimizers.apply_updates(params, updates)
    assert float(loss(params)) < 1e-2


def test_adamw_decays_weights():
    opt = optimizers.adamw(0.1, weight_decay=0.5)
    params = {"w": jnp.asarray([10.0])}
    state = opt.init(params)
    zero_grad = {"w": jnp.asarray([0.0])}
    for _ in range(20):
        updates, state = opt.update(zero_grad, state, params)
        params = optimizers.apply_updates(params, updates)
    assert float(jnp.abs(params["w"][0])) < 10.0  # decayed toward zero


@given(st.lists(st.floats(-100, 100, allow_nan=False, width=32),
                min_size=1, max_size=16),
       st.floats(0.1, 10.0))
@settings(max_examples=50, deadline=None)
def test_clip_by_global_norm_property(vals, max_norm):
    g = {"x": jnp.asarray(vals, jnp.float32)}
    clipped, norm = optimizers.clip_by_global_norm(g, max_norm)
    out_norm = float(optimizers.global_norm(clipped))
    assert out_norm <= max_norm * (1 + 1e-4) + 1e-6
    if float(norm) <= max_norm:  # no-op when under the limit
        np.testing.assert_allclose(np.asarray(clipped["x"]),
                                   np.asarray(g["x"]), rtol=1e-5)


def test_warmup_cosine_schedule():
    fn = optimizers.Schedules.warmup_cosine(1.0, 10, 100)
    assert float(fn(jnp.asarray(0))) == 0.0
    assert float(fn(jnp.asarray(10))) == pytest.approx(1.0, abs=0.02)
    assert float(fn(jnp.asarray(100))) == pytest.approx(0.0, abs=1e-6)
    assert float(fn(jnp.asarray(5))) == pytest.approx(0.5, abs=1e-6)


# --------------------------------------------------------------------------
# straggler detection / mitigation
# --------------------------------------------------------------------------
def test_straggler_detector_flags_slow_host():
    det = StragglerDetector(num_hosts=8, patience=3, warmup_steps=5)
    rng = np.random.default_rng(0)
    flagged_at = None
    for step in range(40):
        t = 1.0 + rng.normal(0, 0.01, 8)
        if step >= 10:
            t[3] = 3.0  # host 3 goes slow
        flags = det.observe(t)
        if flags.any():
            flagged_at = step
            assert flags[3] and flags.sum() == 1
            break
    assert flagged_at is not None and flagged_at < 25


def test_straggler_detector_quiet_on_uniform_noise():
    det = StragglerDetector(num_hosts=4, warmup_steps=5)
    rng = np.random.default_rng(1)
    assert not any(
        det.observe(1.0 + rng.normal(0, 0.02, 4)).any() for _ in range(50))


@given(st.integers(1, 64), st.integers(2, 16))
@settings(max_examples=50, deadline=None)
def test_rebalance_preserves_batch(batch, hosts):
    rng = np.random.default_rng(batch * hosts)
    flagged = rng.random(hosts) < 0.3
    sizes = rebalance_shards(batch, flagged)
    assert sizes.sum() == batch
    assert (sizes >= 0).all()
