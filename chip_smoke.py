#!/usr/bin/env python3
"""Smoke run of the served spike path on a TPU.

    python chip_smoke.py [--seed N]          # one chip
    python chip_smoke.py --mesh [--seed N]   # four chips, sharded path only

One chip: the Table IV MNIST net (784-256-10, random weights from
``--seed``) deployed on the 32x32-cluster, 1024-neuron accelerator geometry,
a (784+1024) x 1024 int32 weight image.

  engine parity  a T=100 batch of procedural digits on every Pallas
                 backend x event gate x K in {1, 8}; each raster must be
                 byte-identical to the ``reference`` backend, and each
                 compiled program must hold the Mosaic kernel
                 (``tpu_custom_call``), i.e. nothing ran interpreted.
  served path    ``AcceleratorSession(backend="pallas", fuse_steps=8)``
                 behind the async frontend, 8 slots x 8-step chunks under
                 the per-example gate; 16 requests of 100 timesteps are
                 pumped until retired, and each request's raster must equal
                 a ``reference`` run of its stimulus.

``--mesh``: the served phase on a (neuron=2, batch=2) mesh over four chips,
compared with the single-device ``reference`` run; it prints each device's
shard of the weight image and fails unless the shards sit on four distinct
devices.

Runs in one process (a chip belongs to one process). Information goes to
earlier lines; the last line of stdout is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``. Without a TPU,
or when any phase fails, it prints ``{"ok": false, ...}`` and exits 1.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import snap_v_snn  # noqa: E402
from repro.core import cerebra_h, coding  # noqa: E402
from repro.core.session import AcceleratorSession  # noqa: E402
from repro.data.mnist import render_digits  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.serving.frontend import FrontendConfig  # noqa: E402
from repro.snn.model import init_params, to_snnetwork  # noqa: E402

HIDDEN = 256          # Table IV's widest hidden layer
STEPS = 100           # Table IV's longest T
PARITY_BATCH = 8
REQUESTS = 16
SLOTS = 8
CHUNK = 8
FUSE = 8
KERNEL_BACKENDS = ("pallas", "pallas-mxu")
GATES = ("batch-tile", "per-example")
FUSE_STEPS = (1, FUSE)
KERNEL_MARK = "tpu_custom_call"


def log(msg: str) -> None:
    print(f"[chip-smoke] {msg}", flush=True)


def device_info() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def build_net(seed: int, hidden: int = HIDDEN):
    """The Table IV net ``784-hidden-10`` with random weights."""
    cfg = snap_v_snn.model_config(hidden)
    return to_snnetwork(init_params(jax.random.key(seed), cfg), cfg)


def digit_stimulus(seed: int, n: int, steps: int = STEPS) -> np.ndarray:
    """(steps, n, 784) int32 Poisson spikes of procedural MNIST digits."""
    labels = np.random.default_rng(seed).integers(0, 10, n)
    images = render_digits(labels, seed=seed).reshape(n, -1)
    return np.asarray(coding.poisson_encode(
        jax.random.key(seed), images, steps, dtype=jnp.int32))


def deploy(net, config, **session_kw):
    sess = AcceleratorSession(config=config, **session_kw)
    sess.deploy("mnist", net)
    return sess


def engine_parity(net, ext, *, config=snap_v_snn.ACCELERATOR,
                  backends=KERNEL_BACKENDS, gates=GATES,
                  fuse_steps=FUSE_STEPS, require_kernel: bool = True) -> dict:
    """Every backend x gate x K raster against ``reference`` on ``ext``.

    Raises AssertionError on the first mismatch, or when a kernel program
    lacks the compiled Mosaic call and ``require_kernel`` is set.
    """
    program = deploy(net, config).models["mnist"].program
    ext = jnp.asarray(ext, jnp.int32)
    want = jax.device_get(cerebra_h.make_engine(program, "reference").run(ext))
    checks, compile_s = 0, 0.0
    for backend in backends:
        for gate in gates:
            for k in fuse_steps:
                engine = (cerebra_h.make_engine(program, backend)
                          .with_gate(gate).with_fuse_steps(k))
                t0 = time.perf_counter()
                compiled = jax.jit(engine.run).lower(ext).compile()
                compile_s += time.perf_counter() - t0
                name = f"{backend}/{gate}/K={k}"
                if require_kernel:
                    assert KERNEL_MARK in compiled.as_text(), (
                        f"{name}: compiled program holds no {KERNEL_MARK}")
                got = jax.device_get(compiled(ext))
                for key in ("spikes", "v_final"):
                    assert np.array_equal(got[key], want[key]), (
                        f"{name}: {key} differs from reference")
                checks += 1
    log(f"engine parity: {checks} rasters byte-identical to reference "
        f"(T={ext.shape[0]}, B={ext.shape[1]}, image "
        f"{program.n_sources}x{config.geometry.n_physical}, "
        f"{int(want['spikes'].sum())} spikes); compile {compile_s:.2f} s")
    return {"checks": checks, "compile_s": compile_s}


def served_path(net, stim, *, config=snap_v_snn.ACCELERATOR, mesh=None,
                require_kernel: bool = True, n_slots: int = SLOTS,
                chunk_steps: int = CHUNK, fuse_steps: int = FUSE) -> dict:
    """``stim`` (T, R, n_inputs): R requests through session + frontend.

    Every request must retire with a raster equal to the ``reference``
    engine's run of its stimulus.
    """
    sess = deploy(net, config, backend="pallas", fuse_steps=fuse_steps,
                  mesh=mesh)
    view = sess.serve("mnist", n_slots=n_slots, chunk_steps=chunk_steps,
                      gate="per-example",
                      frontend=FrontendConfig(queue_capacity=stim.shape[1]))
    server, fe = view.server, view.frontend
    if require_kernel:
        engine = server.engine
        carry = engine.init_carry(n_slots)
        text = jax.jit(engine.step_chunk).lower(
            carry, jnp.zeros((chunk_steps, n_slots, engine.n_inputs),
                             jnp.int32),
            jnp.ones((chunk_steps, n_slots), jnp.int32)).as_text()
        assert KERNEL_MARK in text, f"served step holds no {KERNEL_MARK}"
    handles = [view.submit(stim[:, i]) for i in range(stim.shape[1])]
    t0 = time.perf_counter()
    fe.pump()
    first_s = time.perf_counter() - t0
    first_steps = server.total_steps
    while not fe.idle:
        fe.pump()
    wall = time.perf_counter() - t0
    program = sess.models["mnist"].program
    solo = jax.device_get(cerebra_h.make_engine(program, "reference").run(
        jnp.asarray(stim, jnp.int32))["spikes"])
    retired = 0
    for i, h in enumerate(handles):
        assert h.state == "done", f"request {i} ended {h.state!r}"
        got = h.result()["spikes"]
        want = view.decode(solo[:, i])["spikes"]
        assert np.array_equal(got, want), (
            f"request {i}: served raster differs from its reference run")
        retired += 1
    steps = server.total_steps
    after = (steps - first_steps) / max(wall - first_s, 1e-9)
    log(f"served path: {retired}/{len(handles)} requests retired, each "
        f"equal to its reference run; {steps} timesteps in {wall:.3f} s; "
        f"first pump (compile included) {first_s:.3f} s, then "
        f"{after:.1f} timesteps/s")
    return {"retired": retired, "steps": steps, "wall_s": wall,
            "first_pump_s": first_s, "server": server}


def mesh_served_path(net, stim, **kw) -> dict:
    """The served phase on a (neuron=2, batch=2) mesh of four devices."""
    from repro.distributed.spike_mesh import make_spike_mesh

    mesh = make_spike_mesh(neuron=2, batch=2)
    out = served_path(net, stim, mesh=mesh, **kw)
    shards = out["server"].engine.weights_sharded.addressable_shards
    for s in shards:
        log(f"weight shard on {s.device}: rows {s.index[0]}, "
            f"cols {s.index[1]}, shape {s.data.shape}")
    devices = {s.device for s in shards}
    assert len(devices) == 4, (
        f"weight image sits on {len(devices)} device(s), expected 4")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mesh", action="store_true",
                    help="four chips: the sharded served path only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    log(f"compile cache: {enable_compile_cache()}")
    dev = device_info()
    log(f"devices: {dev}")
    need = 4 if args.mesh else 1
    if dev["platform"] != "tpu" or dev["count"] < need:
        print(json.dumps({"ok": False, "error": (
            f"needs {need} TPU device(s); JAX found {dev['count']} "
            f"{dev['platform']} device(s)")}))
        return 1

    net = build_net(args.seed)
    phases = ({"mesh served path": lambda: mesh_served_path(
                   net, digit_stimulus(args.seed + 1, REQUESTS))}
              if args.mesh else
              {"engine parity": lambda: engine_parity(
                   net, digit_stimulus(args.seed, PARITY_BATCH)),
               "served path": lambda: served_path(
                   net, digit_stimulus(args.seed + 1, REQUESTS))})
    failed = []
    for name, run in phases.items():
        t0 = time.perf_counter()
        try:
            run()
        except Exception as e:  # noqa: BLE001 - report every phase
            failed.append(name)
            log(f"FAILED {name}: {type(e).__name__}: {e}")
        log(f"{name}: {time.perf_counter() - t0:.2f} s")
    if failed:
        print(json.dumps({"ok": False, "failed": failed}))
        return 1
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
