"""Mesh-sharded engine parity: scale-out must never change a bit.

The acceptance criterion of the mesh subsystem: for a >= 2x2
(neuron x batch) mesh — CI fakes 8 CPU devices via
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` — every
``MeshSpikeEngine`` output (spike rasters, final carries, decoded
outputs) is BYTE-identical to the single-device engine, for every
backend x reset mode, including ``step_chunk`` masked-slot semantics,
fused multi-model ``run_all``, and streaming ``feed()`` through a
sharded ``SpikeServer``. On a single-device run (the plain tier-1 leg)
the multi-device cases skip and the degenerate 1x1-mesh cases still
exercise the shard_map path end to end.

The served-path parity cases run for both neuron models (``neuron``):
one-state LIF and current-based LIF, whose synaptic current ``i`` shards
like ``v``.
"""

import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import BACKENDS, DecaySpec, SpikeEngine
from repro.core.session import AcceleratorSession
from repro.distributed.spike_mesh import (MeshSpikeEngine, make_spike_mesh)
from repro.serving.snn import SpikeServer

from conftest import make_random_net

THRESH = 1 << 16
RESET_MODES = ("zero", "subtract", "hold")
NEURONS = ("lif", "cuba")

# deliberately ragged: neither n_phys nor B divides a 2-way mesh axis
RAGGED_SHAPES = [
    # (B, n_inputs, n_phys)
    (3, 37, 48),
    (1, 1, 1),
    (5, 200, 130),
]


def _mesh(neuron, batch):
    need = neuron * batch
    if len(jax.devices()) < need:
        pytest.skip(
            f"needs {need} devices; run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=8")
    return make_spike_mesh(neuron=neuron, batch=batch)


def _engine_pair(rng, *, backend="reference", reset="subtract", decay=None,
                 B=4, n_in=37, n_phys=48, mesh=None, density=0.3,
                 wmax=1 << 13, neuron="lif"):
    S = n_in + n_phys
    W = jnp.asarray(
        (rng.random((S, n_phys)) < density)
        * rng.integers(-wmax, wmax, (S, n_phys)), jnp.int32)
    kw = dict(decay=decay or DecaySpec.shift(0.25), threshold_raw=THRESH,
              reset_mode=reset, backend=backend,
              syn_decay=DecaySpec.shift(0.75) if neuron == "cuba" else None)
    single = SpikeEngine(W, n_in, **kw)
    sharded = MeshSpikeEngine(W, n_in, mesh=mesh, **kw)
    return single, sharded


def _assert_run_parity(single, sharded, ext):
    a = single.run(ext)
    b = sharded.run(ext)
    assert a.keys() == b.keys()
    for k in a:
        av, bv = np.asarray(a[k]), np.asarray(b[k])
        assert av.dtype == bv.dtype == np.int32
        np.testing.assert_array_equal(av, bv)


# --------------------------------------------------------------------------
# Construction contracts
# --------------------------------------------------------------------------

def test_make_spike_mesh_validation():
    with pytest.raises(ValueError, match="devices"):
        make_spike_mesh(neuron=len(jax.devices()) + 1, batch=2)
    with pytest.raises(ValueError, match=">= 1"):
        make_spike_mesh(neuron=0)
    mesh = make_spike_mesh(neuron=1, batch=1)
    assert mesh.shape == {"neuron": 1, "batch": 1}


def test_mesh_engine_requires_snn_axes(rng):
    wrong = jax.make_mesh((1,), ("data",))
    with pytest.raises(ValueError, match="neuron"):
        _engine_pair(rng, mesh=wrong)


@pytest.mark.parametrize("neuron", NEURONS)
def test_to_mesh_is_drop_in(rng, neuron):
    """`engine.to_mesh(mesh)` re-hosts the same program: same config, a
    MeshSpikeEngine, and bit-identical outputs."""
    mesh = make_spike_mesh(neuron=1, batch=1)
    single, _ = _engine_pair(rng, mesh=mesh, neuron=neuron)
    hosted = single.to_mesh(mesh)
    assert isinstance(hosted, MeshSpikeEngine)
    assert hosted.reset_mode == single.reset_mode
    assert hosted.n_phys == single.n_phys
    assert hosted.carry_keys == single.carry_keys
    ext = (np.random.default_rng(1).random((5, 3, single.n_inputs))
           < 0.35).astype(np.int32)
    _assert_run_parity(single, hosted, ext)


def test_server_mesh_kwarg_rehosts_engine(rng):
    mesh = make_spike_mesh(neuron=1, batch=1)
    single, _ = _engine_pair(rng, mesh=mesh)
    srv = SpikeServer(single, n_slots=2, chunk_steps=3, mesh=mesh)
    assert isinstance(srv.engine, MeshSpikeEngine)
    # already-mesh engines pass through untouched
    srv2 = SpikeServer(srv.engine, n_slots=2, chunk_steps=3, mesh=mesh)
    assert srv2.engine is srv.engine


# --------------------------------------------------------------------------
# Degenerate 1x1 mesh: the shard_map path runs in every environment
# --------------------------------------------------------------------------

@pytest.mark.parametrize("neuron", NEURONS)
def test_degenerate_mesh_run_parity(rng, neuron):
    mesh = make_spike_mesh(neuron=1, batch=1)
    single, sharded = _engine_pair(rng, mesh=mesh, neuron=neuron)
    ext = (rng.random((6, 5, single.n_inputs)) < 0.35).astype(np.int32)
    _assert_run_parity(single, sharded, ext)


@pytest.mark.parametrize("neuron", NEURONS)
def test_degenerate_mesh_single_step_parity(rng, neuron):
    """`step` on the mesh engine routes through the sharded path and
    matches the single-device step bit-for-bit."""
    mesh = make_spike_mesh(neuron=1, batch=1)
    single, sharded = _engine_pair(rng, mesh=mesh, neuron=neuron)
    carry = single.init_carry(3)
    ext_t = (rng.random((3, single.n_inputs)) < 0.4).astype(np.int32)
    c1, s1 = single.step(carry, jnp.asarray(ext_t))
    c2, s2 = sharded.step(carry, jnp.asarray(ext_t))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    assert c1.keys() == c2.keys() == set(single.carry_keys)
    for k in c1:
        np.testing.assert_array_equal(np.asarray(c1[k]), np.asarray(c2[k]))


@pytest.mark.parametrize("neuron", NEURONS)
def test_degenerate_mesh_chunk_parity(rng, neuron):
    mesh = make_spike_mesh(neuron=1, batch=1)
    single, sharded = _engine_pair(rng, mesh=mesh, reset="zero",
                                   neuron=neuron)
    carry = single.init_carry(3)
    ext = (rng.random((4, 3, single.n_inputs)) < 0.35).astype(np.int32)
    act = (rng.random((4, 3)) < 0.6).astype(np.int32)
    c1, s1 = single.step_chunk(carry, ext, act)
    c2, s2 = sharded.step_chunk(carry, ext, act)
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    assert c1.keys() == c2.keys() == set(single.carry_keys)
    for k in c1:
        np.testing.assert_array_equal(np.asarray(c1[k]), np.asarray(c2[k]))


# --------------------------------------------------------------------------
# The acceptance sweep: >= 2x2 mesh, every backend x reset mode, batch
# run AND streaming feed through a sharded SpikeServer
# --------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("reset", RESET_MODES)
def test_mesh_parity_backend_reset_sweep(rng, backend, reset):
    mesh = _mesh(2, 2)
    single, sharded = _engine_pair(rng, backend=backend, reset=reset, B=5,
                                   mesh=mesh)
    T = 7
    ext = (rng.random((T, 5, single.n_inputs)) < 0.35).astype(np.int32)
    _assert_run_parity(single, sharded, ext)

    # streaming: the same raster dribbled raggedly through a SHARDED
    # server must reproduce the one-shot batch raster byte for byte
    srv = SpikeServer(sharded, n_slots=3, chunk_steps=3)
    uid = srv.attach()
    pieces, t0 = [], 0
    for n in (2, 4, 1):  # ragged boundaries, sum == T
        pieces.append(srv.feed({uid: ext[t0:t0 + n, 0]})[uid]["spikes"])
        t0 += n
    assert t0 == T
    got = np.concatenate(pieces, axis=0)
    want = np.asarray(single.run(ext)["spikes"])[:, 0]
    assert got.dtype == want.dtype == np.int32
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("B,n_in,n_phys", RAGGED_SHAPES)
def test_mesh_parity_ragged_shapes(rng, B, n_in, n_phys):
    """Neuron/batch padding to mesh multiples must never leak into
    results — including n_phys=1 on a 2-way neuron axis."""
    mesh = _mesh(2, 2)
    single, sharded = _engine_pair(rng, B=B, n_in=n_in, n_phys=n_phys,
                                   mesh=mesh)
    ext = (rng.random((6, B, n_in)) < 0.35).astype(np.int32)
    _assert_run_parity(single, sharded, ext)


def test_mesh_parity_mul_decay(rng):
    """The Cerebra-S truncating-multiply PDU shards exactly too."""
    mesh = _mesh(2, 2)
    single, sharded = _engine_pair(
        rng, decay=DecaySpec.mul(int(round(0.7 * 65536))), mesh=mesh)
    ext = (rng.random((6, 4, single.n_inputs)) < 0.35).astype(np.int32)
    _assert_run_parity(single, sharded, ext)


def test_mesh_parity_wide_mesh_uses_all_devices(rng):
    """The full 8-device 2x4 shape of the CI leg."""
    mesh = _mesh(2, 4)
    single, sharded = _engine_pair(rng, B=6, mesh=mesh)
    assert sharded.device_count == 8
    ext = (rng.random((5, 6, single.n_inputs)) < 0.35).astype(np.int32)
    _assert_run_parity(single, sharded, ext)


# --------------------------------------------------------------------------
# step_chunk masked-slot semantics on the mesh
# --------------------------------------------------------------------------

@pytest.mark.parametrize("neuron", NEURONS)
def test_mesh_step_chunk_masked_slots(rng, neuron):
    """Inactive slots keep their carry bit-for-bit across a sharded chunk
    step; active slots advance exactly as the single-device chunk does —
    including carries chained across successive chunks."""
    mesh = _mesh(2, 2)
    single, sharded = _engine_pair(rng, reset="zero", B=5, mesh=mesh,
                                   neuron=neuron)
    c1 = single.init_carry(5)
    c2 = sharded.init_carry(5)
    for _ in range(3):
        ext = (rng.random((4, 5, single.n_inputs)) < 0.35).astype(np.int32)
        act = (rng.random((4, 5)) < 0.5).astype(np.int32)
        c1, s1 = single.step_chunk(c1, ext, act)
        c2, s2 = sharded.step_chunk(c2, ext, act)
        np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
        assert c1.keys() == c2.keys() == set(single.carry_keys)
        for k in c1:
            np.testing.assert_array_equal(np.asarray(c1[k]),
                                          np.asarray(c2[k]))


def test_mesh_closed_loop_through_server(rng):
    """run_closed_loop (T=1 masked chunks, feedback through the host)
    produces the same trajectory on a sharded server."""
    mesh = _mesh(2, 2)
    single, sharded = _engine_pair(rng, reset="subtract", mesh=mesh)

    def controller(spikes_t):
        return (spikes_t[: single.n_inputs] ^ 1).astype(np.int32)

    outs = []
    for engine in (single, sharded):
        srv = SpikeServer(engine, n_slots=2, chunk_steps=4)
        uid = srv.attach()
        ext0 = np.zeros((single.n_inputs,), np.int32)
        ext0[::3] = 1
        outs.append(srv.run_closed_loop(uid, controller, 6, ext0))
    np.testing.assert_array_equal(outs[0]["spikes"], outs[1]["spikes"])
    np.testing.assert_array_equal(outs[0]["counts"], outs[1]["counts"])


# --------------------------------------------------------------------------
# Fused multi-model run_all + streaming churn on a sharded session
# --------------------------------------------------------------------------

def test_session_run_all_sharded_parity(rng):
    """Co-resident fused models on a mesh session decode bit-identically
    to the single-device session (spikes, counts, predictions, costs)."""
    mesh = _mesh(2, 2)
    nets = [make_random_net(rng),
            make_random_net(rng, n_in=12, n_neurons=32)]
    key = jax.random.key(0)
    plain, meshed = AcceleratorSession(), AcceleratorSession(mesh=mesh)
    for sess in (plain, meshed):
        sess.deploy("a", nets[0])
        sess.deploy("b", nets[1])
    inputs = {"a": rng.random((3, 20)).astype(np.float32),
              "b": rng.random((3, 12)).astype(np.float32)}
    ra = plain.run_all(inputs, 10, key)
    rb = meshed.run_all(inputs, 10, key)
    for name in ("a", "b"):
        for k in ("spikes", "output_counts", "predictions", "cycles",
                  "sops", "row_fetches"):
            np.testing.assert_array_equal(np.asarray(ra[name][k]),
                                          np.asarray(rb[name][k]))


@pytest.mark.parametrize("neuron", NEURONS)
def test_session_streaming_churn_sharded_parity(rng, neuron):
    """Attach/feed/detach churn across co-resident models' streams on a
    sharded session server matches the single-device server exactly."""
    mesh = _mesh(2, 2)
    syn = 0.75 if neuron == "cuba" else None
    nets = [make_random_net(rng, syn_decay_rate=syn),
            make_random_net(rng, n_in=12, n_neurons=32, syn_decay_rate=syn)]
    sessions = [AcceleratorSession(), AcceleratorSession(mesh=mesh)]
    for sess in sessions:
        sess.deploy("a", nets[0])
        sess.deploy("b", nets[1])
    chunks_a = [(rng.random((n, 20)) < 0.4).astype(np.int32)
                for n in (3, 1, 4)]
    chunks_b = [(rng.random((n, 12)) < 0.4).astype(np.int32)
                for n in (2, 5)]
    results = []
    for sess in sessions:
        va = sess.serve("a", n_slots=3, chunk_steps=3)
        vb = sess.serve("b", n_slots=3, chunk_steps=3)
        assert va.server is vb.server
        ua = va.attach()
        ub = vb.attach()
        outs = [va.feed(ua, chunks_a[0]),
                vb.feed(ub, chunks_b[0]),
                va.feed(ua, chunks_a[1])]
        va.detach(ua)            # churn: evict a, re-attach fresh
        ua2 = va.attach()
        outs.append(va.feed(ua2, chunks_a[2]))
        outs.append(vb.feed(ub, chunks_b[1]))
        results.append(outs)
    for o_plain, o_mesh in zip(*results):
        for k in ("spikes", "output_counts", "predictions"):
            np.testing.assert_array_equal(np.asarray(o_plain[k]),
                                          np.asarray(o_mesh[k]))


def _async_frontend_parity(rng, mesh, neuron):
    """Requests served through an AsyncSpikeFrontend over a SHARDED
    server are byte-identical to the single-device engine's one-shot
    run — the async front door composes with the mesh unchanged."""
    from repro.serving.frontend import AsyncSpikeFrontend

    single, sharded = _engine_pair(rng, mesh=mesh, neuron=neuron)
    rasters = [(rng.random((T, single.n_inputs)) < 0.35).astype(np.int32)
               for T in (7, 4, 9, 2)]
    server = SpikeServer(sharded, n_slots=2, chunk_steps=3)
    fe = AsyncSpikeFrontend(server, queue_capacity=len(rasters))
    handles = [fe.submit(r) for r in rasters]
    assert fe.drain()["counts"]["done"] == len(rasters)
    for h, r in zip(handles, rasters):
        want = np.asarray(single.run(r[:, None, :])["spikes"])[:, 0]
        np.testing.assert_array_equal(h.result()["spikes"], want)


@pytest.mark.parametrize("neuron", NEURONS)
def test_async_frontend_degenerate_mesh_parity(rng, neuron):
    _async_frontend_parity(rng, make_spike_mesh(neuron=1, batch=1), neuron)


@pytest.mark.parametrize("neuron", NEURONS)
def test_async_frontend_sharded_parity(rng, neuron):
    _async_frontend_parity(rng, _mesh(2, 2), neuron)


@pytest.mark.parametrize("neuron", NEURONS)
@pytest.mark.parametrize("shape", [(1, 1), (2, 2)])
def test_mesh_server_detach_keeps_carry_sharding(rng, shape, neuron):
    """Evicting from a sharded server zeroes the freed slots in place:
    the carry keeps its sharding and its bytes match the single-device
    server's, and the next occupants power up from zero."""
    mesh = _mesh(*shape)
    single, sharded = _engine_pair(rng, mesh=mesh, neuron=neuron)
    noise = {u: (rng.random((4, single.n_inputs)) < 0.4).astype(np.int32)
             for u in range(4)}
    fresh = (rng.random((5, single.n_inputs)) < 0.35).astype(np.int32)
    want = np.asarray(single.run(fresh[:, None, :])["spikes"])[:, 0]
    carries = []
    for engine in (single, sharded):
        server = SpikeServer(engine, n_slots=4, chunk_steps=3)
        for u in range(4):
            server.attach(u)
        server.feed(noise)
        shardings = {k: x.sharding for k, x in server.carry.items()}
        server.detach(1)
        server.detach_many([3, 0])
        for k, x in server.carry.items():
            assert x.sharding.is_equivalent_to(shardings[k], x.ndim), k
            np.testing.assert_array_equal(np.asarray(x)[[0, 1, 3]], 0)
        carries.append({k: np.asarray(x) for k, x in server.carry.items()})
        for u in ("x", "y", "z"):
            server.attach(u)
        out = server.feed({u: fresh for u in ("x", "y", "z")})
        for u in ("x", "y", "z"):
            np.testing.assert_array_equal(out[u]["spikes"], want)
    assert carries[0].keys() == carries[1].keys() == set(single.carry_keys)
    for k in carries[0]:
        assert carries[0][k].tobytes() == carries[1][k].tobytes()


# --------------------------------------------------------------------------
# ensure_host_devices: faked devices on CPU only
# --------------------------------------------------------------------------

def test_ensure_host_devices_on_cpu_sets_the_flag(monkeypatch):
    from repro.distributed import spike_mesh

    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=2")
    n = len(jax.devices()) + 1  # more than this process already has
    with pytest.raises(RuntimeError, match="before the first jax device"):
        spike_mesh.ensure_host_devices(n)
    assert os.environ["XLA_FLAGS"] == (
        f"--xla_force_host_platform_device_count={n}")


def test_ensure_host_devices_on_an_accelerator_counts_real_chips(
        monkeypatch):
    """Off CPU nothing is faked: the flag is withdrawn again and --devices
    N needs N real devices."""
    from repro.distributed import spike_mesh

    chips = [types.SimpleNamespace(platform="tpu")] * 4
    monkeypatch.setattr(spike_mesh.jax, "devices", lambda: chips)
    monkeypatch.setenv("XLA_FLAGS", "--xla_dump_to=/dev/null")
    spike_mesh.ensure_host_devices(4)
    assert os.environ["XLA_FLAGS"] == "--xla_dump_to=/dev/null"
    monkeypatch.delenv("XLA_FLAGS")
    with pytest.raises(RuntimeError, match="real chips"):
        spike_mesh.ensure_host_devices(8)
    assert "XLA_FLAGS" not in os.environ
