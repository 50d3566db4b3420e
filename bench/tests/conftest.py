"""Shared pieces of the benchmark's CPU tests: the import path and cells
cut to a size a CPU test can run in seconds (the configuration's own
limits and mixes, tiny widths)."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import pytest  # noqa: E402

#: the published v5e peaks, for runs that skip the look for a chip
PEAKS = {"flops_per_s": 1.97e14, "hbm_bytes_per_s": 8.19e11}


def tiny_cell(workload: str, **where):
    """``workload`` (found as ``spec.load_cell`` finds it, with ``where``'s
    ``bench``, ``root`` and ``bench_dir``) at a CPU test's size."""
    from foembench import spec

    cell = spec.load_cell(workload, **where)
    cell.config.update(vocab_size=300, num_topics=8, minibatch_docs=16,
                       bucket_len=16, active_topics=4, num_docs=16 * 4 + 32,
                       mean_doc_tokens=12, num_tokens=5000, buffer_rows=300)
    if "serve" in cell.config:
        cell.config["serve"].update(max_batch=16, bucket_multiple=8,
                                    max_len=16, vocab_pad=64,
                                    knee_docs_per_s=200.0)
    mix = cell.traffic
    mix["topics"].update(true_topics=8, topic_support=64)
    if mix["kind"] == "stream":
        mix.update(heldout_docs=32, max_cycle_minibatches=4,
                   trace_seconds=0.3)
    else:
        mix.update(doc_tokens=[4, 16], checked_requests=10_000,
                   trace_seconds=0.3)
        if "knee_docs_per_s" in mix:
            mix["knee_docs_per_s"] = 200.0
    return cell


def run_tiny(workload, tmp_path, *, seconds: float = 0.6,
             trace: bool = False, **kw):
    """One run of a tiny cell (a workload's name, or a cell made by
    :func:`tiny_cell`) with the look for a chip skipped."""
    from foembench import runner

    cell = tiny_cell(workload) if isinstance(workload, str) else workload
    kw.setdefault("say", lambda s: None)
    return runner.run_cell(cell, 20121029, seconds, trace,
                           require_tpu=False, peaks=PEAKS,
                           out_dir=str(tmp_path), **kw)


@pytest.fixture
def tiny():
    return run_tiny


@pytest.fixture
def tiny_cell_of():
    return tiny_cell
