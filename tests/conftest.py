"""Shared fixtures. NOTE: no XLA_FLAGS here — tests run on the single real
CPU device (DESIGN.md: only the dry-run forces 512 placeholder devices).

When ``hypothesis`` is not installed, a stub is injected so the property
test modules still collect; every ``@given`` test then skips with a clear
message instead of failing the whole collection run.
"""

import sys
import types

import numpy as np
import pytest

try:  # pragma: no cover - exercised only on machines without hypothesis
    import hypothesis  # noqa: F401
except ImportError:
    _SKIP_REASON = "hypothesis is not installed; property-based test skipped"

    class _StubStrategy:
        """Inert strategy object; supports the chaining API shapes use."""

        def _chain(self, *args, **kwargs):
            return self

        map = filter = flatmap = _chain

        def __call__(self, *args, **kwargs):
            return self

    def _strategy_factory(*args, **kwargs):
        return _StubStrategy()

    def _given(*args, **kwargs):
        def decorate(fn):
            # Bare-varargs signature so pytest never tries to resolve the
            # hypothesis-provided parameters as fixtures.
            def skipper(*a, **k):
                pytest.skip(_SKIP_REASON)

            skipper.__name__ = getattr(fn, "__name__", "property_test")
            skipper.__doc__ = fn.__doc__
            skipper.pytestmark = list(getattr(fn, "pytestmark", []))
            return skipper

        return decorate

    def _settings(*args, **kwargs):
        def decorate(fn):
            return fn

        return decorate

    def _assume(condition):
        return True

    _stub = types.ModuleType("hypothesis")
    _stub.given = _given
    _stub.settings = _settings
    _stub.assume = _assume
    _stub.example = _settings
    _stub.note = lambda *a, **k: None
    _stub.HealthCheck = types.SimpleNamespace(
        too_slow=None, data_too_large=None, filter_too_much=None
    )
    _st = types.ModuleType("hypothesis.strategies")
    _st.__getattr__ = lambda name: _strategy_factory
    _stub.strategies = _st
    sys.modules["hypothesis"] = _stub
    sys.modules["hypothesis.strategies"] = _st


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def assert_slot_state(server, uid, ref, carry=None):
    """``uid``'s slot holds the one-shot ``run``'s final state (``ref``,
    batch row 0): ``v``, and ``i`` for current-based neurons. A current
    dropped on the way can leave every spike in place, so served-path
    tests check the state too. ``carry``: a parked snapshot's arrays in
    place of the server's slot."""
    if carry is None:
        slot = server.slot_of(uid)
        carry = {k: np.asarray(x)[slot] for k, x in server.carry.items()}
    assert carry.keys() == set(server.engine.carry_keys)
    for k in set(carry) - {"spikes"}:
        np.testing.assert_array_equal(carry[k],
                                      np.asarray(ref[f"{k}_final"])[0],
                                      err_msg=k)


def make_random_net(rng, n_in=20, n_neurons=48, density=0.25, out=10,
                    decay_rate=0.25, reset_mode="zero", scale=0.5,
                    syn_decay_rate=None):
    """Random recurrent-ish SNNetwork with an output slice (current-based
    neurons with ``syn_decay_rate``)."""
    from repro.core.lif import LIFParams
    from repro.core.network import SNNetwork

    W = ((rng.random((n_in + n_neurons, n_neurons)) < density)
         * rng.normal(0.0, scale, (n_in + n_neurons, n_neurons)))
    params = LIFParams(decay_rate=decay_rate, threshold=1.0,
                       reset_mode=reset_mode, syn_decay_rate=syn_decay_rate)
    return SNNetwork(
        n_inputs=n_in, n_neurons=n_neurons, weights=W.astype(np.float32),
        params=params, output_slice=(n_neurons - out, n_neurons))


def make_ff_net(rng, sizes=(20, 24, 10), decay_rate=0.25, scale=0.6,
                syn_decay_rate=None):
    from repro.core.lif import LIFParams
    from repro.core.network import feedforward

    ws = [rng.normal(0.0, scale / np.sqrt(a), (a, b)).astype(np.float32)
          for a, b in zip(sizes[:-1], sizes[1:])]
    return feedforward(ws, LIFParams(decay_rate=decay_rate,
                                     syn_decay_rate=syn_decay_rate))
