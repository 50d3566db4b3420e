"""CPU test of the reader of the device row tier's share of the rows: the
right value on a synthetic ctx, and nothing where the program's steps lack
the fields (a program without the tier) or the cell is of the other kind."""
from types import SimpleNamespace

import pytest

from foembench import spec

NAME = "train.device_tier_share"


def test_device_tier_share_reader():
    read = spec.load_reader(NAME)
    steps = [SimpleNamespace(rows=300, tier_rows=300),
             SimpleNamespace(rows=100, tier_rows=0)]
    assert read({"kind": "train", "steps": steps}) == pytest.approx(75.0)
    assert read({"kind": "train", "steps": steps[:1]}) == pytest.approx(100.0)
    dropped = [SimpleNamespace(rows=0, tier_rows=0)]
    assert read({"kind": "train", "steps": dropped}) is None
    old = [SimpleNamespace(sweeps=10, h2d_bytes=1)]
    assert read({"kind": "train", "steps": old}) is None
    assert read({"kind": "train", "steps": []}) is None
    assert read({"kind": "serve", "batch_log": [{"filled": 1}]}) is None


def test_device_tier_share_is_declared_for_the_training_cells():
    declared = {m["name"]: m for m in spec.load_benchmark()["per_layer"]}
    assert declared[NAME]["workloads"] == ["kos_k100.train",
                                           "pubmed_k10k.train"]
    assert declared[NAME]["moves"] == "train_tokens_per_s"
    assert declared[NAME]["layer"] == "streaming trainer"
