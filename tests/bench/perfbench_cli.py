"""Run one benchmark cell on the CPU, for tests: the harness's look for a
chip is skipped, and ``--fault`` breaks the timed path underneath.

    python perfbench_cli.py --root DIR --workload W --seed N --seconds S
        --trace 0|1 [--fault NAME] [--require-tpu]
"""

import argparse
import pathlib
import sys
import time

STARTED = time.perf_counter()
REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path[:1] = [str(REPO / "src"), str(REPO)]


def _break(fault: str) -> None:
    """Patch ``SpikeEngine.step_chunk``, the timed path's device step."""
    import jax.numpy as jnp

    from repro.core.engine import SpikeEngine

    step = SpikeEngine.step_chunk

    def stale_state(self, carry, ext, active=None):
        # the step's outputs, but its state returned unchanged
        _, spikes = step(self, carry, ext, active)
        return carry, spikes

    def half_batch(self, carry, ext, active=None):
        # every other slot left out of the step
        active = jnp.asarray(active, jnp.int32)
        keep = (jnp.arange(active.shape[1]) % 2 == 0).astype(jnp.int32)
        return step(self, carry, ext, active * keep[None, :])

    def altered_answer(self, carry, ext, active=None):
        # one spike of the first active (step, slot) flipped where produced
        new, spikes = step(self, carry, ext, active)
        act = jnp.asarray(active)
        flat = jnp.argmax(act.reshape(-1) != 0)
        t, b = flat // act.shape[1], flat % act.shape[1]
        return new, spikes.at[t, b, 0].set(1 - spikes[t, b, 0])

    def altered_potential(self, carry, ext, active=None):
        # one membrane potential of the first slot moved by one LSB
        new, spikes = step(self, carry, ext, active)
        return dict(new, v=new["v"].at[0, 0].add(1)), spikes

    SpikeEngine.step_chunk = {"stale-state": stale_state,
                              "half-batch": half_batch,
                              "altered-answer": altered_answer,
                              "altered-potential": altered_potential}[fault]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", required=True)
    ap.add_argument("--fault")
    ap.add_argument("--require-tpu", action="store_true")
    args, rest = ap.parse_known_args()
    from bench import harness

    if args.fault:
        _break(args.fault)
    return harness.main(rest, root=pathlib.Path(args.root), started=STARTED,
                        require_tpu=args.require_tpu)


if __name__ == "__main__":
    sys.exit(main())
