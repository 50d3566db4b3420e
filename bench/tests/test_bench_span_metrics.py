"""CPU tests of the readers of the program's host-stage fields: each returns
the right value on a synthetic ctx, and nothing where the program does not
keep the field (a program older than the fields) or the cell is of the
other kind."""
from types import SimpleNamespace

import pytest

from foembench import spec


def _step(**kw):
    base = dict(lock_wait_seconds=0.0, fetch_seconds=0.0, host_seconds=0.0,
                h2d_bytes=0, d2h_bytes=0)
    base.update(kw)
    return SimpleNamespace(**base)


TRAIN_STEPS = [
    _step(lock_wait_seconds=1.0, fetch_seconds=0.5, host_seconds=2.0,
          h2d_bytes=3_000_000, d2h_bytes=1_000_000),
    _step(lock_wait_seconds=3.0, fetch_seconds=1.5, host_seconds=4.0,
          h2d_bytes=5_000_000, d2h_bytes=3_000_000),
]

#: metric -> (field it reads, value on TRAIN_STEPS)
TRAIN = {
    "train.store_lock_wait_ms": ("lock_wait_seconds", 2000.0),
    "train.host_fetch_ms": ("fetch_seconds", 1000.0),
    "train.step_host_ms": ("host_seconds", 3000.0),
    "train.host_copy_mb": ("h2d_bytes", 6.0),
}


def _launch(waits, prep, stage_in, device_wait, h2d_bytes):
    return {"filled": len(waits), "capacity": 8, "launch_seconds": 0.01,
            "queue_wait_s": waits, "prep_seconds": prep,
            "stage_in_seconds": stage_in,
            "device_wait_seconds": device_wait, "h2d_bytes": h2d_bytes}


# 200 waits of 1..200 ms over two launches: nearest-rank p50 is the 100th
SERVE_LOG = [_launch([i / 1e3 for i in range(1, 101)], 0.004, 0.002, 0.005,
                     2_000_000),
             _launch([i / 1e3 for i in range(101, 201)], 0.006, 0.004, 0.007,
                     4_000_000)]

SERVE = {
    "serve.queue_wait_p50_ms": ("queue_wait_s", 100.0),
    "serve.host_prep_ms": ("prep_seconds", 5.0),
    "serve.stage_in_ms": ("stage_in_seconds", 3.0),
    "serve.device_wait_ms": ("device_wait_seconds", 6.0),
    "serve.host_copy_mb": ("h2d_bytes", 3.0),
}


@pytest.mark.parametrize("name", sorted(TRAIN))
def test_train_reader(name):
    read = spec.load_reader(name)
    field, want = TRAIN[name]
    assert read({"kind": "train", "steps": TRAIN_STEPS}) == pytest.approx(want)
    old = [SimpleNamespace(**{k: v for k, v in vars(m).items() if k != field})
           for m in TRAIN_STEPS]
    assert read({"kind": "train", "steps": old}) is None
    assert read({"kind": "train", "steps": []}) is None
    assert read({"kind": "serve", "batch_log": SERVE_LOG}) is None


@pytest.mark.parametrize("name", sorted(SERVE))
def test_serve_reader(name):
    read = spec.load_reader(name)
    field, want = SERVE[name]
    assert read({"kind": "serve", "batch_log": SERVE_LOG}) == pytest.approx(want)
    old = [{k: v for k, v in b.items() if k != field} for b in SERVE_LOG]
    assert read({"kind": "serve", "batch_log": old}) is None
    assert read({"kind": "serve", "batch_log": []}) is None
    assert read({"kind": "train", "steps": TRAIN_STEPS}) is None


def test_new_metrics_are_declared_for_their_cells():
    bench = spec.load_benchmark()
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name in TRAIN:
        assert declared[name]["workloads"] == ["kos_k100.train",
                                               "pubmed_k10k.train"]
    for name in SERVE:
        assert declared[name]["workloads"] == ["kos_k100.serve_poisson",
                                               "kos_k100.serve_x4"]


def test_replica_skew_reader():
    read = spec.load_reader("serve.replica_skew")
    # the busiest of 4 replicas took 60 of 160 batches: 1.5x an even 40
    pool = {"kind": "serve", "batch_log": SERVE_LOG,
            "dispatch": {0: 60, 1: 50, 2: 30, 3: 20}}
    assert read(pool) == pytest.approx(50.0)
    assert read(dict(pool, dispatch={0: 7, 1: 7})) == pytest.approx(0.0)
    assert read(dict(pool, dispatch={0: 0, 1: 0})) is None
    assert read({"kind": "serve", "batch_log": SERVE_LOG}) is None
    assert read({"kind": "train", "steps": TRAIN_STEPS}) is None
    declared = {m["name"]: m for m in spec.load_benchmark()["per_layer"]}
    assert declared["serve.replica_skew"]["workloads"] == ["kos_k100.serve_x4"]
    assert declared["serve.replica_skew"]["layer"] == "replica balancer"
