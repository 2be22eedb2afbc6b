"""The current-based LIF neuron (two states: synaptic current ``i`` and
membrane ``v``) through every layer of the program.

Pinned here, at tiny sizes (``n_phys`` 128, T <= 24):

  * every backend x gate x K advances spikes, ``v`` and ``i`` bit-exactly
    like a plain ``lax.scan`` over :func:`repro.core.lif.cuba_step_fixed`,
    through ragged chunks and masked slots;
  * the slot server zeroes a freed slot's current, snapshots carry it,
    and a LIF snapshot never restores onto a current-based slot (nor the
    reverse);
  * the session never fuses LIF and current-based models into one engine;
  * the mesh engine on a 2x2 host mesh is bit-identical;
  * a one-state deployment builds what it built before the current
    existed: carry ``{v, spikes}``, the ``spike_timestep_fused`` kernel
    with its seven operands;
  * the float software model and the fixed-point engine agree on spike
    counts.
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cerebra_h, cerebra_s
from repro.core.engine import BACKENDS, GATES, DecaySpec, SpikeEngine
from repro.core.lif import LIFParams, cuba_step_fixed, cuba_step_float
from repro.core.network import SNNetwork
from repro.core.session import AcceleratorSession
from repro.core.software import run_software
from repro.obs import MetricsRegistry
from repro.serving.connector import CarrySnapshot, InMemoryCarryConnector
from repro.serving.snn import SpikeServer

from conftest import make_random_net

THRESH = 1 << 16
PARAMS = LIFParams(decay_rate=0.5, threshold=1.0, reset_mode="zero",
                   syn_decay_rate=0.75)
N_IN, N_PHYS = 40, 128


def _weights(seed, n_in=N_IN, n_phys=N_PHYS, density=0.3, wmax=1 << 14):
    r = np.random.default_rng(seed)
    S = n_in + n_phys
    W = (r.random((S, n_phys)) < density) * r.integers(-wmax, wmax,
                                                       (S, n_phys))
    return jnp.asarray(W, jnp.int32)


def _engine(W, *, backend="reference", gate="batch-tile", K=1,
            n_in=N_IN):
    return SpikeEngine(W, n_in, decay=DecaySpec.shift(PARAMS.decay_rate),
                       syn_decay=DecaySpec.shift(PARAMS.syn_decay_rate),
                       threshold_raw=THRESH, reset_mode="zero",
                       backend=backend, gate=gate, fuse_steps=K)


def _plain(W, carry, ext, active):
    """The plain reference: scan ``cuba_step_fixed``; an inactive (step,
    slot) keeps its state and emits nothing."""
    def step(c, xs):
        ext_t, act_t = xs
        src = jnp.concatenate([ext_t, c["spikes"]], axis=-1)
        acc = jnp.dot(src, W, preferred_element_type=jnp.int32)
        state, spikes = cuba_step_fixed({"v": c["v"], "i": c["i"]}, acc,
                                        PARAMS)
        keep = act_t[:, None] != 0
        new = dict(state, spikes=spikes)
        return ({k: jnp.where(keep, new[k], c[k]) for k in c},
                jnp.where(keep, spikes, 0))

    return jax.lax.scan(step, carry, (ext, active))


def _chunks(seed, B=3, lengths=(11, 13)):
    r = np.random.default_rng(seed)
    out = []
    for T in lengths:
        ext = jnp.asarray(r.random((T, B, N_IN)) < 0.3, jnp.int32)
        act = jnp.asarray(r.random((T, B)) < 0.8, jnp.int32)
        out.append((ext, act))
    return out


def _assert_carry_equal(got, want):
    assert set(got) == set(want) == {"v", "i", "spikes"}
    for k in want:
        np.testing.assert_array_equal(np.asarray(got[k]),
                                      np.asarray(want[k]))


CASES = [(b, g, K) for b in BACKENDS for g in GATES for K in (1, 8)]


@pytest.mark.parametrize("backend,gate,K", CASES,
                         ids=[f"{b}-{g}-K{k}" for b, g, k in CASES])
def test_engine_matches_plain_cuba_scan(backend, gate, K):
    """Ragged chunks (11 then 13 steps, neither a multiple of K) with
    masked slots: spikes, v and i bit-exact, the state carried across the
    chunk boundary."""
    W = _weights(1)
    eng = _engine(W, backend=backend, gate=gate, K=K)
    carry = want = eng.init_carry(3)
    assert set(carry) == {"v", "i", "spikes"}
    active_seen = 0
    for ext, act in _chunks(2):
        carry, spikes = eng.step_chunk(carry, ext, act)
        want, want_spikes = _plain(W, want, ext, act)
        np.testing.assert_array_equal(np.asarray(spikes),
                                      np.asarray(want_spikes))
        _assert_carry_equal(carry, want)
        active_seen += int(np.asarray(want_spikes).sum())
    assert active_seen > 0
    assert np.asarray(want["i"]).any() and np.asarray(want["v"]).any()


@pytest.mark.parametrize("backend", BACKENDS)
def test_run_returns_final_current(backend):
    W = _weights(3)
    eng = _engine(W, backend=backend, gate="per-example", K=8)
    ext = _chunks(4, lengths=(19,))[0][0]
    out = eng.run(ext)
    want, spikes = _plain(W, eng.init_carry(3), ext,
                          jnp.ones(ext.shape[:2], jnp.int32))
    np.testing.assert_array_equal(np.asarray(out["spikes"]),
                                  np.asarray(spikes))
    np.testing.assert_array_equal(np.asarray(out["v_final"]),
                                  np.asarray(want["v"]))
    np.testing.assert_array_equal(np.asarray(out["i_final"]),
                                  np.asarray(want["i"]))


def test_rehosting_keeps_the_current():
    eng = _engine(_weights(5))
    for other in (eng.with_gate("per-example"), eng.with_fuse_steps(8)):
        assert other.syn_decay == eng.syn_decay
        assert other.carry_keys == ("v", "spikes", "i")


def _cuba_net(seed, n_in=20, n_neurons=40, out=8):
    net = make_random_net(np.random.default_rng(seed), n_in=n_in,
                          n_neurons=n_neurons, out=out, scale=0.6)
    return SNNetwork(n_inputs=net.n_inputs, n_neurons=net.n_neurons,
                     weights=net.weights, params=PARAMS,
                     output_slice=net.output_slice)


def _server(engine, **kw):
    return SpikeServer(engine, n_slots=2, chunk_steps=4, **kw)


def _raster(seed, T, n_in):
    r = np.random.default_rng(seed)
    return (r.random((T, n_in)) < 0.4).astype(np.int32)


def test_server_churn_zeroes_the_current():
    """A freed slot powers up with i = 0 too: the next occupant's raster
    equals a solo run of its input."""
    prog = cerebra_h.compile_network(_cuba_net(6))
    eng = cerebra_h.make_engine(prog)
    srv = _server(eng)
    a, b = srv.attach("a"), srv.attach("b")
    srv.feed({a: _raster(1, 9, 20), b: _raster(2, 6, 20)})
    slot = srv.slot_of(a)
    assert np.asarray(srv.carry["i"][slot]).any()
    srv.detach(a)
    for k in ("v", "i", "spikes"):
        assert not np.asarray(srv.carry[k][slot]).any()
    c = srv.attach("c")
    assert srv.slot_of(c) == slot
    x = _raster(3, 10, 20)
    got = srv.feed({c: x})[c]["spikes"]
    want = eng.run(jnp.asarray(x[:, None, :]))["spikes"][:, 0]
    np.testing.assert_array_equal(got, np.asarray(want))


def test_snapshot_round_trip_carries_the_current():
    """Snapshot mid-stream, serialize, restore on a fresh server: the
    stream continues byte-identically, and the connector's byte counter
    counts the current's bytes."""
    prog = cerebra_h.compile_network(_cuba_net(7))
    eng = cerebra_h.make_engine(prog)
    x = _raster(4, 20, 20)
    want = np.asarray(eng.run(jnp.asarray(x[:, None, :]))["spikes"][:, 0])
    srv = _server(eng)
    uid = srv.attach("s")
    first = srv.feed({uid: x[:9]})[uid]["spikes"]
    snap = srv.snapshot_stream(uid)
    assert set(snap.arrays) == {"v", "spikes", "i"}
    assert snap.arrays["i"].any()
    assert snap.slot_params["syn_decay_rate"] == 0.75
    reg = MetricsRegistry()
    conn = InMemoryCarryConnector().instrument(reg)
    conn.insert(uid, snap)
    blob = snap.to_bytes()
    assert reg.counter("snn_connector_bytes_total").labels(
        op="snapshot").value == len(blob)
    lif_blob = CarrySnapshot(
        stream_id=snap.stream_id, slot_params=snap.slot_params,
        arrays={k: snap.arrays[k] for k in ("v", "spikes")},
        meta=snap.meta).to_bytes()
    assert len(blob) - len(lif_blob) >= snap.arrays["i"].nbytes
    other = _server(eng)
    other.attach_stream(conn, uid)
    rest = other.feed({uid: x[9:]})[uid]["spikes"]
    np.testing.assert_array_equal(np.concatenate([first, rest]), want)


def test_lif_and_cuba_snapshots_do_not_cross():
    cuba = cerebra_h.make_engine(cerebra_h.compile_network(_cuba_net(8)))
    lif_net = _cuba_net(8)
    lif_net = SNNetwork(n_inputs=lif_net.n_inputs,
                        n_neurons=lif_net.n_neurons,
                        weights=lif_net.weights,
                        params=LIFParams(decay_rate=0.5, threshold=1.0),
                        output_slice=lif_net.output_slice)
    lif = cerebra_h.make_engine(cerebra_h.compile_network(lif_net))
    assert not lif.has_current and lif.carry_keys == ("v", "spikes")
    srv_c, srv_l = _server(cuba), _server(lif)
    for src, dst in ((srv_c, srv_l), (srv_l, srv_c)):
        uid = src.attach("x")
        src.feed({uid: _raster(5, 5, 20)})
        snap = CarrySnapshot.from_bytes(src.snapshot_stream(uid).to_bytes())
        with pytest.raises(ValueError, match="syn_decay"):
            dst.attach_stream(snap, uid="y")
    # an old snapshot without the field reads as LIF
    snap = srv_l.snapshot_stream("x")
    assert "syn_decay_kind" not in snap.slot_params
    _server(lif).attach_stream(snap, uid="z")
    # a snapshot that carries a current cannot land on a LIF slot
    forged = CarrySnapshot(stream_id="f", slot_params=snap.slot_params,
                           arrays=dict(snap.arrays, i=snap.arrays["v"]))
    with pytest.raises(ValueError, match="synaptic current"):
        forged.check_compatible(srv_l.slot_params())


def test_session_keeps_lif_and_cuba_apart():
    base = _cuba_net(9)
    lif = SNNetwork(n_inputs=base.n_inputs, n_neurons=base.n_neurons,
                    weights=base.weights,
                    params=LIFParams(decay_rate=0.5, threshold=1.0),
                    output_slice=base.output_slice)
    sess = AcceleratorSession()
    sess.deploy("lif", lif)
    sess.deploy("cuba", base)
    v_lif, v_cuba = sess.serve("lif", n_slots=2), sess.serve("cuba",
                                                             n_slots=2)
    assert v_lif.server is not v_cuba.server
    assert set(v_lif.server.carry) == {"v", "spikes"}
    assert set(v_cuba.server.carry) == {"v", "spikes", "i"}
    key = jax.random.key(0)
    x = np.random.default_rng(1).random((2, base.n_inputs))
    both = sess.run_all({"lif": x, "cuba": x}, 12, key)
    for name, net in (("lif", lif), ("cuba", base)):
        solo = AcceleratorSession()
        solo.deploy(name, net)
        np.testing.assert_array_equal(
            np.asarray(both[name]["output_counts"]),
            np.asarray(solo.run(name, x, 12, key)["output_counts"]))


def test_server_reports_carry_bytes():
    reg = MetricsRegistry()
    eng = cerebra_h.make_engine(cerebra_h.compile_network(_cuba_net(10)))
    SpikeServer(eng, n_slots=3, chunk_steps=4, metrics=reg)
    g = reg.gauge("snn_server_carry_bytes")
    n_phys = eng.n_phys
    for state in ("v", "i", "spikes"):
        assert g.labels(state=state).value == 3 * n_phys * 4


def test_one_state_deployment_is_unchanged():
    """A LIF deployment's carry is {v, spikes} and its fused chunk step
    holds one ``spike_timestep_fused`` call with seven operands and three
    results; a current-based one holds ``spike_timestep_fused_syn`` with
    the current in and out."""
    import jax.extend.core as jex

    def pallas_calls(engine):
        carry = engine.init_carry(2)
        ext = jnp.zeros((8, 2, engine.n_inputs), jnp.int32)
        act = jnp.ones((8, 2), jnp.int32)
        jaxpr = jax.make_jaxpr(engine._chunk_impl)(
            engine._scan_weights(), carry, ext, act)
        found = []

        def walk(jx):
            for eqn in jx.eqns:
                if eqn.primitive.name == "pallas_call":
                    found.append((eqn.params["name"],
                                  len(eqn.invars), len(eqn.outvars)))
                for v in eqn.params.values():
                    for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                        if isinstance(sub, jex.ClosedJaxpr):
                            walk(sub.jaxpr)
                        elif isinstance(sub, jex.Jaxpr):
                            walk(sub)

        walk(jaxpr.jaxpr)
        return carry, found

    base = _cuba_net(11)
    lif = SNNetwork(n_inputs=base.n_inputs, n_neurons=base.n_neurons,
                    weights=base.weights,
                    params=LIFParams(decay_rate=0.5, threshold=1.0),
                    output_slice=base.output_slice)
    for net, name, n_in, n_out, keys in (
            (lif, "spike_timestep_fused", 7, 3, {"v", "spikes"}),
            (base, "spike_timestep_fused_syn", 8, 4, {"v", "spikes", "i"})):
        sess = AcceleratorSession(backend="pallas", fuse_steps=8)
        sess.deploy("m", net)
        server = sess.serve("m", n_slots=2, gate="per-example").server
        assert set(server.carry) == keys
        carry, found = pallas_calls(server.engine)
        assert found == [(name, n_in, n_out)]


def test_float_model_agrees_with_fixed_point_on_spike_counts():
    """The float software model (exact leaks, float weights) against the
    Q16.16 engine. The leaks are exact shift rates, so the two differ
    only by weight rounding (under 2^-17 a weight) and the shifts'
    rounding toward minus infinity (under 1 LSB, 2^-16, a decay): a
    membrane that lands within a few LSB of the threshold can fire in one
    and not the other, and the recurrence carries such a flip on. Per
    neuron, the spike counts over 64 streams of 48 steps agree to within
    3% of the busiest neuron's count; the totals to within 1%."""
    net = _cuba_net(12, n_in=30, n_neurons=60, out=10)
    ext = jnp.asarray(np.random.default_rng(2).random((48, 64, 30)) < 0.3,
                      jnp.int32)
    sw = np.asarray(run_software(net, ext)["spikes"]).sum(axis=(0, 1))
    prog = cerebra_h.compile_network(net)
    hw = np.asarray(cerebra_h.run(prog, ext)["spikes"]).sum(axis=(0, 1))
    hw = hw[np.asarray(prog.placement.neuron_to_physical)]
    assert sw.sum() > 1000
    assert np.abs(sw - hw).max() <= 0.03 * sw.max()
    assert abs(sw.sum() - hw.sum()) <= 0.01 * sw.sum()


def test_float_step_integrates_the_current():
    state = {"v": jnp.zeros(3), "i": jnp.asarray([0.4, 0.0, 2.0])}
    new, spikes = cuba_step_float(state, jnp.asarray([0.2, 0.5, 0.0]),
                                  PARAMS)
    np.testing.assert_allclose(np.asarray(new["i"]), [0.3, 0.5, 0.5])
    np.testing.assert_array_equal(np.asarray(spikes), [0, 0, 0])


def test_cerebra_s_compiles_the_current_decay():
    prog = cerebra_s.compile_network(_cuba_net(13))
    assert prog.syn_decay_raw == 1 << 14       # retain 0.25
    eng = cerebra_s.make_engine(prog)
    assert eng.syn_decay == DecaySpec.mul(1 << 14)
    out = eng.run(jnp.asarray(np.random.default_rng(3).random((6, 2, 20))
                              < 0.4, jnp.int32))
    assert "i_final" in out


MESH = textwrap.dedent("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.core.engine import DecaySpec, SpikeEngine
    from repro.distributed.spike_mesh import make_spike_mesh
    assert len(jax.devices()) == 4
    r = np.random.default_rng(0)
    W = jnp.asarray((r.random((37 + 48, 48)) < 0.3)
                    * r.integers(-1 << 14, 1 << 14, (85, 48)), jnp.int32)
    mesh = make_spike_mesh(2, 2)
    for backend in ("reference", "pallas"):
        eng = SpikeEngine(W, 37, decay=DecaySpec.shift(0.5),
                          syn_decay=DecaySpec.shift(0.75),
                          threshold_raw=1 << 16, reset_mode="zero",
                          backend=backend, gate="per-example")
        me = eng.to_mesh(mesh)
        assert me.syn_decay == eng.syn_decay
        ext = jnp.asarray(r.random((10, 3, 37)) < 0.3, jnp.int32)
        act = jnp.asarray(r.random((10, 3)) < 0.8, jnp.int32)
        a, b = eng.run(ext), me.run(ext)
        for k in ("spikes", "v_final", "i_final"):
            np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))
        ca, sa = eng.step_chunk(eng.init_carry(3), ext, act)
        cb, sb = me.step_chunk(me.init_carry(3), ext, act)
        np.testing.assert_array_equal(np.asarray(sa), np.asarray(sb))
        assert set(cb) == {"v", "i", "spikes"}
        for k in ca:
            np.testing.assert_array_equal(np.asarray(ca[k]), np.asarray(cb[k]))
    print("mesh ok")
""")


def test_mesh_engine_on_2x2_host_devices_is_bit_identical():
    """On four faked host devices, in a child process so the device-count
    flag never reaches this one."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..", "src"),
                    env.get("PYTHONPATH", "")]))
    p = subprocess.run([sys.executable, "-c", MESH], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    assert "mesh ok" in p.stdout
