"""Training substrate: optimizers and schedules (``snn/train.py``)."""

from repro.training import optimizers  # noqa: F401
