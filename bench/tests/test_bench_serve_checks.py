"""The tiny serving cells on the CPU, run end to end with the look for a
chip skipped: one engine (``serve_poisson``) and a thread replica pool
(``serve_x4``, whose four replicas share the CPU's one device here).  The
program's θ agree with the plain reference, its control (the program's own
bfloat16 φ path) comes out not correct, and so does each fault a serving
cell can have, planted in every replica."""
import pytest

from foembench import faults

#: each serving cell and the replicas its mix serves
CELLS = {"kos_k100.serve_poisson": 1, "kos_k100.serve_x4": 4}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_program_run_is_correct(tiny, tmp_path, cell):
    r = tiny(cell, tmp_path, seconds=1.0)
    assert r["correct"], r["checks"]
    m = r["metrics"]
    assert m["serve_docs_per_s"]["value"] > 0
    assert m["serve_p99_ms"]["value"] > 0
    assert r["failed"] == 0 and r["attempted"] > 50
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_bfloat16_control_is_not_correct(tiny, tmp_path, cell):
    r = tiny(cell, tmp_path, control=True)
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_traced_run_reports_front_metrics(tiny, tmp_path, cell):
    notes = []
    r = tiny(cell, tmp_path, seconds=1.5, trace=True, say=notes.append)
    assert r["correct"], r["checks"]
    assert 0 < r["metrics"]["serve.batch_fill"]["value"] <= 100
    assert r["metrics"]["serve.launch_ms"]["value"] > 0
    pool = [n for n in notes if "thread ReplicaPool replicas" in n]
    assert len(pool) == (CELLS[cell] > 1)
    # the balancer's skew is read from a pool's dispatch; an engine has none
    if CELLS[cell] > 1:
        assert r["metrics"]["serve.replica_skew"]["value"] >= 0
    else:
        assert "serve.replica_skew" not in r["metrics"]


@pytest.mark.parametrize("name", sorted(faults.SERVE))
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_fault_is_not_correct(tiny, tmp_path, cell, name):
    r = tiny(cell, tmp_path, fault=faults.SERVE[name])
    assert not r["correct"], r["checks"]
