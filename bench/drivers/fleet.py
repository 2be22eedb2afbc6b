"""A fleet of closed control loops, one persistent stream per plant.

Entry: ``ModelStream.feed_many``, one process and one thread, no front
door. Set-up attaches one stream per plant; every tick then encodes each
plant's error as Poisson spikes, feeds ``tick_steps`` steps of every
plant in one ``feed_many``, decodes each plant's command from its output
populations (E+ then E-, the two halves of the output slice) and moves
the plant by it, so the next tick's input depends on this tick's answer.
Each plant is an integrator ``x += dt * gain * u``; its setpoint is
redrawn every ``every_ticks`` ticks, the plants staggered evenly over
that period, so every seed offers the same work at the same times.

Ticks start at most once a ``period_ms``, the deployment's loop period:
a tick that ends early waits for the next period, one that ends late
starts the next tick at once with the newest plant state, as a loop
that drops stale sensor frames does. A tick is timed from the start of
its ``feed_many`` to the decoded commands of every plant; the loop rate
is the ticks the window completed over its seconds, so a stall that the
95th percentile passes over still shows there. A fixed sample of plants, drawn from the seed,
keeps its whole input and raster for the check, and its membrane
potentials are read after the window with ``SpikeServer.snapshot_stream``.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from bench import seeds
from bench.reference import Check


class Driver:
    """Drives one fleet mix; see the module docstring."""

    def __init__(self, root, dep, traffic: dict, seed: int, seconds: float,
                 trace: bool):
        import jax

        self._annotate = jax.profiler.TraceAnnotation
        self.view = dep.view
        self.seconds, self.trace = seconds, trace
        P = int(traffic["plants"])
        self.T = int(traffic["tick_steps"])
        self.period_s = float(traffic["period_ms"]) / 1e3
        self.warm_ticks = int(traffic["warm_ticks"])
        self.plant, self.enc = traffic["plant"], traffic["encoder"]
        self.sp = traffic["setpoints"]
        lo, hi = dep.net.output_slice
        self.n_out = (hi - lo) // 2
        self.uids = [self.view.attach() for _ in range(P)]
        if any(self.view.slot_of(u) is None for u in self.uids):
            raise ValueError(f"{P} plants need {P} slots; the server has "
                             f"{self.view.server.n_slots}")
        self.x = np.zeros(P)
        self.setpoint = np.zeros(P)
        self.phase = (np.arange(P) * int(self.sp["every_ticks"])) // P
        self.rng_sp = seeds.rng(seed, "setpoints")
        self.rng_enc = seeds.rng(seed, "poisson")
        self.sample = np.sort(seeds.rng(seed, "sample").choice(
            P, size=min(P, int(traffic["sample_plants"])), replace=False))
        self.tick = 0             # ticks since attach, warm-up included
        self.ticks = 0            # ticks inside the window
        self.lat: list[float] = []
        self.err: list[float] = []
        self.window_s = 0.0
        self._gc: list[tuple[int, float]] = []   # window's collections
        self._gc_t = 0.0
        self.steps = 0            # stream timesteps inside the window
        self.failed = 0
        self._ext: list[np.ndarray] = []    # per tick (S, T, n_in) uint8
        self._spk: list[np.ndarray] = []    # per tick (S, T, hi) uint8
        # traced runs: per-chunk source events of every plant
        self._lo, self._hi = self.view.phys_slice
        self._last = np.zeros((P, self._hi - self._lo), np.int64)
        self._work: list[tuple] = []

    # -- one tick ----------------------------------------------------------
    def _encode(self) -> np.ndarray:
        every = int(self.sp["every_ticks"])
        due = (self.tick + self.phase) % every == 0
        self.setpoint[due] = self.rng_sp.uniform(
            self.sp["low"], self.sp["high"], int(due.sum()))
        err = self.setpoint - self.x
        rate = np.clip(np.stack([np.maximum(err, 0), np.maximum(-err, 0)], 1)
                       / self.enc["err_scale"], 0.0, 1.0)
        u = self.rng_enc.random((len(self.uids), self.T, 2))
        self.err.append(float(np.abs(err).mean()))
        return (u < rate[:, None, :]).astype(np.int32)

    def _step(self, in_window: bool) -> None:
        ext = self._encode()
        inputs = dict(zip(self.uids, ext))
        n = self.n_out
        with self._annotate("bench.feed"):
            t0 = time.perf_counter()
            out = self.view.feed_many(inputs)
            counts = np.stack([out[u]["output_counts"] for u in self.uids])
            u = self.plant["u_max"] * (counts[:, :n].mean(1)
                                       - counts[:, n:2 * n].mean(1)) / self.T
            dt = time.perf_counter() - t0
        self.x += self.plant["dt"] * self.plant["gain"] * u
        self._ext.append(ext[self.sample].astype(np.uint8))
        self._spk.append(np.stack([out[self.uids[k]]["spikes"][:, :self._hi]
                                   for k in self.sample]).astype(np.uint8))
        if self.trace:
            self._count_work(ext, out, in_window)
        if in_window:
            self.lat.append(dt)
            self.ticks += 1
            self.steps += len(self.uids) * self.T
        self.tick += 1

    def _count_work(self, ext, out, in_window: bool) -> None:
        spk = np.stack([out[u]["spikes"][:, self._lo:self._hi]
                        for u in self.uids]).astype(np.int64)
        prev = np.concatenate([self._last[:, None], spk[:, :-1]], axis=1)
        self._last = spk[:, -1]
        if in_window:
            cs = self.view.server.chunk_steps
            nch = -(-self.T // cs)
            pad = ((0, 0), (0, nch * cs - self.T), (0, 0))
            P = len(self.uids)
            self._work.append((
                np.pad(ext, pad).reshape(P, nch, cs, -1).sum((0, 2)),
                np.pad(prev, pad).reshape(P, nch, cs, -1).sum((0, 2))))

    # -- set-up ------------------------------------------------------------
    def warm(self) -> None:
        """The loop's first ticks: the chunk step runs at the window's own
        slot batch and chunk count."""
        for _ in range(self.warm_ticks):
            self._step(False)

    # -- the window --------------------------------------------------------
    def window(self) -> None:
        clock = time.perf_counter
        gc.callbacks.append(self._on_gc)
        try:
            with self._annotate("bench.window"):
                t0 = clock()
                due = 0.0
                while True:
                    now = clock() - t0
                    wait = min(due, self.seconds) - now
                    if wait > 0:
                        time.sleep(wait)
                        now = clock() - t0
                    if now >= self.seconds:
                        break
                    due = now + self.period_s
                    self._step(True)
        finally:
            gc.callbacks.remove(self._on_gc)
        self.window_s = now

    def _on_gc(self, phase: str, info: dict) -> None:
        """Records each garbage collection in the window: (generation,
        seconds), to tell the host's stalls apart."""
        if phase == "start":
            self._gc_t = time.perf_counter()
        else:
            self._gc.append((info["generation"],
                             time.perf_counter() - self._gc_t))

    # -- results -----------------------------------------------------------
    def values(self) -> dict:
        """End-to-end values this traffic measures."""
        return {"tick_p95_ms": float(np.percentile(self.lat, 95)) * 1e3,
                "loop_rate_hz": self.ticks / self.window_s}

    @property
    def attempted(self) -> int:
        return self.ticks

    def unanswered(self) -> int:
        """``feed_many`` answers every plant of a tick before the next
        tick starts, or the run raises: none is left unanswered."""
        return 0

    def checks(self):
        ext = np.concatenate(self._ext, axis=1)
        spk = np.concatenate(self._spk, axis=1)
        server = self.view.server
        return [Check(ext=ext[i], served=spk[i],
                      potentials=server.snapshot_stream(
                          self.uids[k]).arrays["v"])
                for i, k in enumerate(self.sample)]

    def info(self) -> list[str]:
        lat = np.asarray(self.lat) * 1e3
        slow = np.sort(lat)[-3:][::-1]
        gen2 = [t for g, t in self._gc if g == 2]
        return [f"plants {len(self.uids)}, ticks {self.ticks} in "
                f"{self.window_s:.4f} s, tick p50 "
                f"{np.percentile(lat, 50):.4f} ms, p95 "
                f"{np.percentile(lat, 95):.4f} ms, slowest "
                f"{', '.join(f'{v:.4f}' for v in slow)} ms",
                f"garbage collections in the window: {len(self._gc)}, "
                f"longest {max((t for _, t in self._gc), default=0) * 1e3:.4f}"
                f" ms; generation 2: {len(gen2)}, "
                f"{sum(gen2) * 1e3:.4f} ms in all",
                f"mean |error| over the window "
                f"{np.mean(self.err[self.warm_ticks:]):.4f}; sampled plants "
                f"{self.sample.tolist()}"]

    def round_work(self):
        """Per-chunk source events of the window, every plant: (external
        events (R, n_in), recurrent events (R, N), streams served (R,)),
        or None in untraced runs."""
        if not self.trace:
            return None
        ext_ev = np.concatenate([e for e, _ in self._work])
        rec_ev = np.concatenate([r for _, r in self._work])
        return ext_ev, rec_ev, np.full(len(ext_ev), len(self.uids), np.int64)
