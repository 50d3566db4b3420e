"""A tiny training cell on the CPU, run end to end with the look for a
chip skipped: the program agrees with the plain reference, its control
(the reference in bfloat16 in the program's place) comes out not correct,
and a traced run reports the per-layer metrics it has the inputs for."""


def test_program_run_is_correct(tiny, tmp_path):
    r = tiny("kos_k100.train", tmp_path)
    assert r["correct"], r["checks"]
    m = r["metrics"]
    assert m["train_tokens_per_s"]["value"] > 0
    assert m["train_heldout_ppl"]["value"] > 1
    assert m["setup_s"]["value"] > 0
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "checks"


def test_bfloat16_control_is_not_correct(tiny, tmp_path):
    r = tiny("kos_k100.train", tmp_path, control=True)
    assert not r["correct"], r["checks"]
    failed = [k for k, c in r["checks"].items() if c["value"] > c["limit"]]
    assert "fold_mass_gap" in failed


def test_traced_run_reports_counter_metrics(tiny, tmp_path):
    r = tiny("kos_k100.train", tmp_path, seconds=1.5, trace=True)
    assert r["correct"], r["checks"]
    assert r["metrics"]["train.sweeps_per_step"]["value"] >= 10
    assert 0 <= r["metrics"]["train.prefetch_miss_share"]["value"] <= 100
    assert 0 < r["metrics"]["train.mfu"]["value"] < 100
    assert r["device"]["window_s"] > 0
    assert "idle_gaps" in r["breakdown"]
