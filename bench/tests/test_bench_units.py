"""CPU tests of the benchmark's own arithmetic: work counts, trace
reduction, peaks, lookup by name, the generator, due-time latency, and the
entry point's refusal without a chip."""
import json
import os
import shutil
import subprocess
import sys
import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

from foembench import device, spec, tracing, traffic, work
from foembench.spec import BENCH_DIR, ROOT


# --------------------------------------------------------------- work counts

def test_sweep_work_matches_hand_count():
    # 3 tokens x 2 lanes: 19 flops and 12 bytes per lane; 2 word rows and
    # 1 doc row of 2 lanes read and written once (8 bytes an entry)
    w = work.sweep(tokens=3, lanes=2, words=2, docs=1)
    assert w.flops == 19 * 3 * 2
    assert w.bytes == 12 * 3 * 2 + 8 * 3 * 2


def test_minibatch_work_splits_dense_and_scheduled_sweeps():
    w = work.minibatch(10, 4, 2, topics=8, active=2, sweeps=5, warmup=2)
    dense = work.sweep(10, 8, 4, 2)
    sched = work.sweep(10, 2, 4, 2)
    assert w.flops == 2 * dense.flops + 3 * sched.flops
    assert w.bytes == 2 * dense.bytes + 3 * sched.bytes


def test_least_seconds_takes_the_binding_term():
    peaks = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    w = work.Work(flops=50.0, bytes=20.0)
    assert w.least_seconds(peaks) == 2.0
    assert w.bound(peaks) == "bandwidth"
    assert work.Work(500.0, 1.0).bound(peaks) == "compute"


# ----------------------------------------------------------- trace reduction

DEV = "/device:TPU:0"


def _synthetic_trace():
    ev = tracing.Event
    return [
        ev("gs_sweep", 0, 10, DEV, "XLA Ops"),
        ev("scheduled_sweep", 5, 10, DEV, "XLA Ops"),   # overlaps the first
        ev("fusion.1", 20, 10, DEV, "XLA Ops"),
        ev("jit_run", 0, 30, DEV, "XLA Modules"),       # not an op line
        ev(tracing.WINDOW_SPAN, 0, 40, "/host:CPU", "python"),
        ev("fetch_rows", 15, 5, "/host:CPU", "python"),
        ev("write_rows", 30, 10, "/host:CPU", "python"),
        ev("whole_step", 0, 40, "/host:CPU", "python"),
    ]


def test_union_busy_idle_share_and_patterns():
    red = tracing.reduce_events(_synthetic_trace())
    assert red.window_ns == (0, 40)
    assert red.busy_s() == pytest.approx(25e-9)
    assert red.idle_share() == pytest.approx(1 - 25 / 40)
    assert red.time_s([r"sweep"]) == pytest.approx(20e-9)
    assert red.count([r"^fusion"]) == 1


def test_breakdown_names_gaps_by_host_activity():
    red = tracing.reduce_events(_synthetic_trace())
    b = red.breakdown()
    assert b["device_ops"][0][0] in ("gs_sweep", "scheduled_sweep",
                                     "fusion.1")
    gaps = dict((n, s) for n, s in b["idle_gaps"])
    assert gaps["write_rows"] == pytest.approx(10e-9)
    assert gaps["fetch_rows"] == pytest.approx(5e-9)


def test_window_clips_device_operations():
    ev = tracing.Event
    events = [ev("op", 0, 100, DEV, "XLA Ops"),
              ev(tracing.WINDOW_SPAN, 50, 20, "/host:CPU", "python")]
    red = tracing.reduce_events(events)
    assert red.busy_s() == pytest.approx(20e-9)
    assert red.idle_share() == pytest.approx(0.0)


def _roofline_ctx(paths, events):
    return {"kind": "train", "sweep_paths": set(paths),
            "trace": tracing.reduce_events(events),
            "trace_work": [work.Work(flops=0.0, bytes=8.19e11 * 5e-9)],
            "peaks": {"flops_per_s": 1.97e14, "hbm_bytes_per_s": 8.19e11}}


def test_sweep_roofline_takes_its_denominator_from_dispatch():
    read = spec.load_reader("train.sweep_roofline")
    trace = _synthetic_trace()
    kernels = [tracing.Event("scheduled_sweep_pallas", 0, 10, DEV, "XLA Ops")]
    # pallas: the kernels' 10 ns; portable: the step program's 30 ns
    assert read(_roofline_ctx({"pallas"}, trace + kernels)) == \
        pytest.approx(50.0)
    assert read(_roofline_ctx({"portable"}, trace + kernels)) == \
        pytest.approx(100.0 * 5 / 30)
    assert read(_roofline_ctx({"pallas", "portable"}, trace + kernels)) == \
        pytest.approx(100.0 * 5 / 30)


def test_sweep_roofline_reports_nothing_when_the_path_is_not_traced():
    read = spec.load_reader("train.sweep_roofline")
    trace = _synthetic_trace()
    # dispatch took the kernels, but no kernel operation is in the trace
    assert read(_roofline_ctx({"pallas"}, trace)) is None
    no_step = [e for e in trace if e.name != "jit_run"]
    assert read(_roofline_ctx({"portable"}, no_step)) is None
    assert read(_roofline_ctx(set(), trace)) is None


# --------------------------------------------------------------------- peaks

def test_peaks_known_device():
    row = device.peaks_for("TPU v5 lite")
    assert row["flops_per_s"] == 1.97e14
    assert row["hbm_bytes_per_s"] == 8.19e11


def test_peaks_refuse_unknown_device():
    with pytest.raises(device.UnknownDevice):
        device.peaks_for("TPU v99 imaginary")


# ------------------------------------------------------------ lookup by name

def test_every_benchmark_cell_resolves():
    bench = spec.load_benchmark()
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"], bench)
        assert cell.end_to_end[0].name == "setup_s"
        assert cell.per_layer and all(m.name in cell.readers
                                      for m in cell.per_layer)


def _checkout(tmp_path):
    """A copy of the benchmark's files under ``tmp_path``, and its
    ``BENCHMARK.json`` as a dict, for a change to add to."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH_DIR, root / "bench",
                    ignore=shutil.ignore_patterns("out", ".jax_cache",
                                                  "__pycache__"))
    bench = json.loads(open(os.path.join(ROOT, "BENCHMARK.json")).read())
    return root, bench


def _add_config(root, bench, name, **changes):
    cfg = json.loads(open(root / "bench/configs/kos_k100.json").read())
    cfg.update(name=name, **changes)
    (root / f"bench/configs/{name}.json").write_text(json.dumps(cfg))
    bench["configs"].append({"name": name, "source": "x",
                             "file": f"bench/configs/{name}.json",
                             "reduced": sorted(changes)})
    return cfg


def _add_mix(root, bench, config, name, chips=1, **changes):
    mix = json.loads(open(root / "bench/traffic/serve_poisson.json").read())
    mix.update(changes)
    (root / f"bench/traffic/{name}.json").write_text(json.dumps(mix))
    bench["workloads"].append({"name": f"{config}.{name}", "config": config,
                               "traffic": name, "chips": chips, "why": "x"})
    return f"{config}.{name}"


def _load(root, bench, workload):
    return spec.load_cell(workload, bench, root=str(root),
                          bench_dir=str(root / "bench"))


def test_new_config_mix_and_metric_found_by_name(tmp_path):
    """A later change adds a configuration, a mix and a metric as new files
    and new entries only; the harness finds them without an edit."""
    root, bench = _checkout(tmp_path)
    cfg = _add_config(root, bench, "kos_k50", num_topics=50)
    workload = _add_mix(root, bench, "kos_k50", "serve_overload", load=1.2)
    (root / "bench/metrics/serve.launches.py").write_text(
        "def read(ctx):\n    return float(len(ctx['batch_log']))\n")
    bench["per_layer"].append({"name": "serve.launches", "unit": "launches",
                               "better": "higher", "source": "program_counter",
                               "layer": "serving front",
                               "moves": "serve_docs_per_s",
                               "workloads": [workload]})
    cell = _load(root, bench, workload)
    assert cell.config["num_topics"] == 50
    assert traffic.offered_rate(cell.config, cell.traffic) == pytest.approx(
        1.2 * cfg["serve"]["knee_docs_per_s"])
    assert cell.readers["serve.launches"]({"batch_log": [{}, {}]}) == 2.0
    assert "serve.launches" in [m.name for m in cell.per_layer]


def test_unknown_names_are_refused():
    with pytest.raises(spec.SpecError):
        spec.load_cell("no_such.cell")
    with pytest.raises(spec.SpecError):
        spec.load_reader("no.such.metric")


def test_default_reference_and_runners_are_the_packages_own():
    from foembench import reference, serve_cell, train_cell

    runner_of = {"stream": train_cell, "open_loop": serve_cell}
    for w in spec.load_benchmark()["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.reference is reference
        assert cell.runner is runner_of[cell.traffic["kind"]]


def test_missing_reference_or_runner_module_is_refused(tmp_path):
    root, bench = _checkout(tmp_path)
    _add_config(root, bench, "kos_blocked", reference="reference_blocked")
    blocked = _add_mix(root, bench, "kos_blocked", "serve_blocked")
    custom = _add_mix(root, bench, "kos_k100", "serve_custom",
                      runner="serve_custom")
    bad = _add_mix(root, bench, "kos_k100", "serve_bad", runner="../run")
    for workload in (blocked, custom, bad):
        with pytest.raises(spec.SpecError):
            _load(root, bench, workload)


def test_chips_must_match_the_replicas_served(tmp_path):
    root, bench = _checkout(tmp_path)
    four_on_one = _add_mix(root, bench, "kos_k100", "serve_x4_on_one",
                           replicas=4)
    one_on_four = _add_mix(root, bench, "kos_k100", "serve_on_four", chips=4)
    for workload in (four_on_one, one_on_four):
        with pytest.raises(spec.SpecError):
            _load(root, bench, workload)
    _add_mix(root, bench, "kos_k100", "serve_x2", chips=2, replicas=2)
    assert _load(root, bench, "kos_k100.serve_x2").chips == 2


def test_mix_knee_comes_before_the_configurations():
    cfg = _cfg()
    mix = dict(load=0.5)
    assert traffic.offered_rate(cfg, mix) == 50.0
    assert traffic.offered_rate(cfg, dict(mix, knee_docs_per_s=300.0)) == 150.0


def test_config_reference_and_mix_runner_run_as_new_files(tmp_path, tiny,
                                                          tiny_cell_of):
    """A configuration brings its own reference, and a mix its own runner,
    as new files under the benchmark directory and new entries in
    ``BENCHMARK.json``; a run goes through them with no file edited."""
    root, bench = _checkout(tmp_path)
    pkg = root / "bench/foembench"
    (pkg / "reference_blocked.py").write_text(
        (pkg / "reference.py").read_text()
        + "\nCALLS = []\n_infer_theta = infer_theta\n\n\n"
        "def infer_theta(*args, **kw):\n    CALLS.append(len(args[0]))\n"
        "    return _infer_theta(*args, **kw)\n")
    shutil.copy(pkg / "serve_cell.py", pkg / "serve_copy.py")
    _add_config(root, bench, "kos_blocked", reference="reference_blocked")
    workload = _add_mix(root, bench, "kos_blocked", "serve_copied",
                        runner="serve_copy")
    for m in bench["end_to_end"]:
        if m["name"] in ("serve_p99_ms", "serve_docs_per_s"):
            m["workloads"].append(workload)
    cell = tiny_cell_of(workload, bench=bench, root=str(root),
                     bench_dir=str(root / "bench"))
    assert cell.reference.__file__ == str(pkg / "reference_blocked.py")
    assert cell.runner.__file__ == str(pkg / "serve_copy.py")
    r = tiny(cell, tmp_path / "out")
    assert r["correct"], r["checks"]
    assert r["metrics"]["serve_p99_ms"]["value"] > 0
    assert sum(cell.reference.CALLS) > 0
    # what was there is untouched: only the new files differ
    import filecmp

    cmp = filecmp.dircmp(BENCH_DIR, root / "bench",
                         ignore=["out", ".jax_cache", "__pycache__"])
    assert not cmp.diff_files
    assert not filecmp.dircmp(os.path.join(BENCH_DIR, "foembench"),
                              pkg, ignore=["__pycache__"]).diff_files


# ----------------------------------------------------------------- generator

def _cfg(**kw):
    base = dict(vocab_size=500, num_topics=10, minibatch_docs=20,
                num_docs=200, mean_doc_tokens=30, num_tokens=5000,
                serve={"knee_docs_per_s": 100.0})
    base.update(kw)
    return base


_TOPICS = dict(base_seed=3, true_topics=10, topic_support=64, word_zipf=1.0,
               topic_dirichlet=0.3)


def test_stream_same_seed_same_documents_other_seed_other_order():
    mix = dict(topics=_TOPICS, doc_dirichlet=0.1, max_cycle_minibatches=4,
               heldout_docs=20)
    a = traffic.lda_corpus(_cfg(), mix, 1)
    b = traffic.lda_corpus(_cfg(), mix, 1)
    c = traffic.lda_corpus(_cfg(), mix, 2)
    assert np.array_equal(a.train.words, b.train.words)
    assert a.train.n == 80 and a.heldout.n == 20
    assert not np.array_equal(a.train.words, c.train.words)
    # the same multiset of documents, in another order
    sig = lambda d: sorted(tuple(d.doc(i)[0]) for i in range(d.n))
    assert sig(a.train) == sig(c.train)
    assert np.array_equal(a.heldout.words, c.heldout.words)


def test_open_loop_same_gaps_and_sizes_in_another_order():
    mix = dict(base_seed=5, word_zipf=1.1, doc_tokens=[4, 16], load=1.0)
    a = traffic.open_loop(_cfg(), mix, 1, seconds=2.0)
    b = traffic.open_loop(_cfg(), mix, 2, seconds=2.0)
    assert a.rate == 100.0
    gaps = lambda r: np.sort(np.diff(np.concatenate([[0.0], r.due])))
    assert np.allclose(gaps(a), gaps(b))
    assert sorted(np.diff(a.docs.indptr)) == sorted(np.diff(b.docs.indptr))
    assert np.all(np.diff(a.due) > 0)
    assert a.keys.dtype == np.uint32 and len(set(map(tuple, a.keys))) == len(a.keys)


# --------------------------------------------------- due-time latency account

class _StallingEngine:
    """Answers each request as it is submitted, except that nothing is
    answered between ``stall_at`` and ``stall_at + stall`` seconds: what is
    submitted then is answered at the stall's end, by one timer (so the
    sender is not slowed by a thread per request)."""

    def __init__(self, stall_at, stall):
        self.t0 = time.perf_counter()
        self.stall = (stall_at, stall_at + stall)
        self.batch_log = []
        self._lock = threading.Lock()
        self._held, self._released = [], False
        self._timers = [threading.Timer(self.stall[1], self._release)]
        self._timers[0].start()

    def _release(self):
        with self._lock:
            held, self._released = self._held, True
        for fut in held:
            fut.set_result(np.ones(2) / 2)

    def submit(self, w, c, key=None):
        fut = Future()
        with self._lock:
            hold = (not self._released
                    and time.perf_counter() - self.t0 >= self.stall[0])
            if hold:
                self._held.append(fut)
        if not hold:
            fut.set_result(np.ones(2) / 2)
        return fut


class _Env:
    class counter:
        armed = False

    out_dir = ""


def test_latency_counts_from_the_due_time_through_a_stall():
    from foembench import serve_cell

    mix = dict(base_seed=5, word_zipf=1.1, doc_tokens=[4, 16], load=1.0)
    reqs = traffic.open_loop(_cfg(), mix, 1, seconds=1.5, rate=200.0)
    n = int(np.searchsorted(reqs.due, 1.5))
    eng = _StallingEngine(stall_at=0.3, stall=0.6)     # answers held till 0.9
    res = serve_cell._window(eng, eng.batch_log, reqs, n, 1.5, _Env(), False,
                            0.0)
    for t in eng._timers:
        t.join(5)
    lat = res["lat"]
    assert res["ok"].all()
    due = reqs.due[:n] + 0.05        # the window opens 50 ms in
    early_in_stall = (due > 0.35) & (due < 0.5)
    # a request due early in the stall waits for its end, from its due time
    assert lat[early_in_stall].min() > 0.35
    assert np.median(lat[due > 1.1]) < 0.2
    assert serve_cell.nearest_rank(lat, 99) > 0.3


def test_nearest_rank_is_an_observed_value():
    from foembench import serve_cell

    v = np.arange(1, 101, dtype=float)
    assert serve_cell.nearest_rank(v, 99) == 99.0
    assert serve_cell.nearest_rank(v, 50) == 50.0
    assert serve_cell.nearest_rank(np.array([3.0]), 99) == 3.0


# --------------------------------------------------------------- entry point

def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload",
         "kos_k100.train", "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert "correct" not in p.stdout
