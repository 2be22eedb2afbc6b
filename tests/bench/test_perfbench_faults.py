"""With the timed path broken underneath, a run of the harness reads
``correct: false``: a step that returns its state unchanged, half of the
slot batch left out, one spike altered where it is produced, and, in a
cell whose configuration checks potentials, one membrane potential
altered where it is produced."""

import pytest

import perfbench_tiny as tiny

FAULTS = ("stale-state", "half-batch", "altered-answer")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("faults"))


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("cell", ["tiny-closed", "tiny-mixed", "tiny-fleet"])
def test_fault_fails_the_comparison(root, cell, fault):
    rc, res, err = tiny.run_cell(root, cell, seconds=0.5, fault=fault)
    assert rc == 0, err[-3000:]
    assert res["correct"] is False
    assert res["checks"]["mismatched_spikes"]["value"] > 0


def test_altered_potential_fails_the_fleet(root):
    rc, res, err = tiny.run_cell(root, "tiny-fleet", seconds=0.5,
                                 fault="altered-potential")
    assert rc == 0, err[-3000:]
    assert res["correct"] is False
    assert res["checks"]["mismatched_potentials"]["value"] > 0
