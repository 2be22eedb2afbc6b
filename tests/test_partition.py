"""Partitioner: logical axes resolve to mesh axes with divisibility
fallbacks and no mesh-axis reuse."""

import jax
from jax.sharding import PartitionSpec

from repro.distributed import partition as part


def _mesh():
    return jax.make_mesh((1, 1), ("data", "model"))


def test_spec_for_divisibility_fallback():
    mesh = _mesh()
    rules = part.PartitionRules(rules={"heads": "model", "embed": "data"})
    # size-1 axes -> everything replicates (single device)
    spec = part.spec_for(("embed", "heads"), (64, 64), mesh, rules)
    assert spec == PartitionSpec()


def test_spec_for_no_axis_reuse():
    mesh = jax.make_mesh((1,), ("model",))
    rules = part.PartitionRules(rules={"a": "model", "b": "model"})
    # both dims want 'model'; only one may take it (here size 1 -> neither)
    spec = part.spec_for(("a", "b"), (8, 8), mesh, rules)
    assert spec == PartitionSpec()
