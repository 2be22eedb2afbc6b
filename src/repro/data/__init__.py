"""Deterministic, stateless, shardable data pipelines.

  mnist  — procedural MNIST (or real IDX files when present)
  events — synthetic event-camera (DVS-gesture-style) sparse spike clips
"""

from repro.data import events, mnist  # noqa: F401
