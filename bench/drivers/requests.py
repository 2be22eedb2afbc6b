"""Request traffic through the async front door, closed loop.

Entry: ``ModelStream.submit`` and ``AsyncSpikeFrontend.pump``, one
process and one thread. ``clients`` clients each submit their next
request, of a length drawn from ``lengths``, in the round their previous
one retired: an offline backlog that keeps the slots full.
"""

from __future__ import annotations

import collections
import dataclasses
import time

import numpy as np

from bench import load, seeds
from bench.reference import Check

DRAIN_LIMIT_S = 120.0


@dataclasses.dataclass
class _Req:
    steps: int
    stim: int             # pool index
    handle: object
    keep: bool            # its raster is read after the window
    admit_round: int = -1
    finished: float | None = None


class Driver:
    """Drives one request mix; see the module docstring."""

    def __init__(self, root, dep, traffic: dict, seed: int, seconds: float,
                 trace: bool):
        import jax

        self._annotate = jax.profiler.TraceAnnotation
        self.view, self.fe = dep.view, dep.view.frontend
        self.traffic, self.seconds, self.trace = traffic, seconds, trace
        stim = dep.config["stimulus"]
        self.pool = load.module(root, "stimuli", stim["kind"]).pool(
            seed, stim["pool"], max(traffic["lengths"]), dep.net.n_inputs)
        self.rng = seeds.rng(seed, "requests")
        self.sample_rate = float(traffic["sample_rate"])
        self.reqs: list[_Req] = []
        self.rounds = 0           # pump rounds inside the window
        self.steps = 0            # timesteps retired inside the window
        self.window_s = 0.0
        self.failed = 0

    # -- set-up ------------------------------------------------------------
    def warm(self) -> None:
        """Fill every slot with one-chunk requests, one more waiting, and
        drain: the chunk step and the per-slot eviction and admission ops
        run at the window's own shapes."""
        n = self.view.server.n_slots + 1
        T = self.view.server.chunk_steps
        for i in range(n):
            self.view.submit(self.pool[i % len(self.pool), :T])
        while not self.fe.idle:
            self.fe.pump()

    # -- the window ----------------------------------------------------------
    def _submit(self, steps) -> None:
        stim = int(self.rng.integers(len(self.pool)))
        keep = self.rng.random() < self.sample_rate or self.trace
        with self._annotate("bench.submit"):
            h = self.view.submit(self.pool[stim, :steps])
        req = _Req(steps=int(steps), stim=stim, handle=h, keep=keep)
        self.reqs.append(req)
        if h.state == "rejected":
            self.failed += 1
        else:
            self._pending.append(req)

    def _after_pump(self, summary, t_end, r) -> None:
        for _ in range(summary["admitted"]):
            req = self._pending.popleft()
            req.admit_round = r
            self._running.append(req)
        if not summary["retired"]:
            return
        still = []
        for req in self._running:
            if req.handle.done:
                req.finished = t_end
                if not req.keep:
                    req.handle = None
                if t_end < self.seconds:
                    self._submit(self._next_len())
            else:
                still.append(req)
        self._running = still

    def _next_len(self) -> int:
        return int(self.rng.choice(self.traffic["lengths"]))

    def window(self) -> None:
        clock = time.perf_counter
        self._pending: collections.deque = collections.deque()
        self._running: list[_Req] = []
        now = 0.0
        with self._annotate("bench.window"):
            t0 = clock()
            for _ in range(int(self.traffic["clients"])):
                self._submit(self._next_len())
            while now < self.seconds:
                with self._annotate("bench.pump"):
                    summary = self.fe.pump()
                now = clock() - t0
                self.steps += summary["steps"]
                self._after_pump(summary, now, self.rounds)
                self.rounds += 1
        self.window_s = now
        # requests still running when the window closes are waited for
        r = self.rounds
        while self._pending or self._running:
            if clock() - t0 > self.seconds + DRAIN_LIMIT_S:
                break
            summary = self.fe.pump()
            self._after_pump(summary, clock() - t0, r)
            r += 1

    # -- results -------------------------------------------------------------
    def values(self) -> dict:
        """End-to-end values this traffic measures."""
        return {"timesteps_per_s": self.steps / self.window_s}

    @property
    def attempted(self) -> int:
        return len(self.reqs)

    def unanswered(self) -> int:
        return sum(1 for r in self.reqs
                   if r.finished is None and r.handle is not None
                   and r.handle.state != "rejected")

    def checks(self):
        out = []
        for r in self.reqs:
            if not r.keep or r.handle is None or r.handle.state == "rejected":
                continue
            res = r.handle.result() if r.finished is not None else None
            out.append(Check(ext=self.pool[r.stim, :r.steps],
                             served=None if res is None else res["spikes"]))
        return out

    def info(self) -> list[str]:
        retired = sum(1 for r in self.reqs if r.finished is not None)
        return [f"requests {len(self.reqs)}, retired {retired}, "
                f"rejected {self.failed}, rounds {self.rounds}, "
                f"timesteps {self.steps} in {self.window_s:.4f} s"]

    def round_work(self):
        """Per-round source events of the window, from the rasters sent and
        received: (external events (R, n_in), recurrent events (R, N),
        streams served (R,)), or None where the rasters were not kept
        (untraced runs). Round r serves chunk j of a request admitted in
        round r - j."""
        if not self.trace:
            return None
        R, chunk = self.rounds, self.view.server.chunk_steps
        n_in = self.pool.shape[2]
        lo, hi = self.view.phys_slice
        N = hi - lo
        ext_ev = np.zeros((R, n_in), np.int64)
        rec_ev = np.zeros((R, N), np.int64)
        streams = np.zeros(R, np.int64)
        for req in self.reqs:
            a = req.admit_round
            if req.finished is None or req.handle is None or a >= R:
                continue
            ext = self.pool[req.stim, :req.steps]
            out = req.handle.result()["spikes"][:, lo:hi]
            prev = np.concatenate([np.zeros((1, N), out.dtype), out[:-1]])
            nch = -(-req.steps // chunk)
            k = min(nch, R - a)
            pad = ((0, nch * chunk - req.steps), (0, 0))
            ext_ev[a:a + k] += np.pad(ext, pad).reshape(
                nch, chunk, n_in)[:k].sum(1)
            rec_ev[a:a + k] += np.pad(prev, pad).reshape(
                nch, chunk, N)[:k].sum(1)
            streams[a:a + k] += 1
        return ext_ev, rec_ev, streams
