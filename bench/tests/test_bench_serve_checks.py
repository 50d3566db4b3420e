"""A tiny serving cell on the CPU, run end to end with the look for a
chip skipped: the program's θ agree with the plain reference, its control
(the program's own bfloat16 φ path) comes out not correct, and so does
each fault a serving cell can have."""
import pytest

from foembench import faults


def test_program_run_is_correct(tiny, tmp_path):
    r = tiny("kos_k100.serve_poisson", tmp_path, seconds=1.0)
    assert r["correct"], r["checks"]
    m = r["metrics"]
    assert m["serve_docs_per_s"]["value"] > 0
    assert m["serve_p99_ms"]["value"] > 0
    assert r["failed"] == 0 and r["attempted"] > 50
    assert list(r)[-1] == "checks"


def test_bfloat16_control_is_not_correct(tiny, tmp_path):
    r = tiny("kos_k100.serve_poisson", tmp_path, control=True)
    assert not r["correct"], r["checks"]


def test_traced_run_reports_front_metrics(tiny, tmp_path):
    r = tiny("kos_k100.serve_poisson", tmp_path, seconds=1.5, trace=True)
    assert r["correct"], r["checks"]
    assert 0 < r["metrics"]["serve.batch_fill"]["value"] <= 100
    assert r["metrics"]["serve.launch_ms"]["value"] > 0


@pytest.mark.parametrize("name", sorted(faults.SERVE))
def test_fault_is_not_correct(tiny, tmp_path, name):
    r = tiny("kos_k100.serve_poisson", tmp_path, fault=faults.SERVE[name])
    assert not r["correct"], r["checks"]
