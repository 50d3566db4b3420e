"""A tiny training run with the timed path broken underneath: each fault
the training cells can have makes ``correct`` come out false."""
import pytest

from foembench import faults


@pytest.mark.parametrize("name", sorted(faults.TRAIN))
def test_fault_is_not_correct(tiny, tmp_path, name):
    r = tiny("kos_k100.train", tmp_path, fault=faults.TRAIN[name])
    assert not r["correct"], r["checks"]
