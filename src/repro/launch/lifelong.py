"""Lifelong train-while-serve driver — the paper's headline scenario.

One `FOEMTrainer` and one `TopicServer`+`ServingEngine` run concurrently
against the same `ParameterStore`, connected only by the versioned
snapshot publish/subscribe protocol::

      trainer thread                         serving side
      ──────────────                         ────────────
      fit_stream(endless minibatches)        ServingEngine launcher
        step → write_rows → ...                │ refresh(): hot-swap to the
        every `publish_every` steps:           │ newest committed version
          SnapshotPublisher.publish()          │ (between launches — zero
          │  WAL flush (COMMIT) under          │ downtime; in-flight batches
          │  the store lock, immutable         │ finish on their pinned
          │  crc-manifested PhiSnapshot        │ epoch)
          ▼                                    ▼
        ShiftDetector.update(residual         every θ resolves as a
        mass, train ppl, φ_k shares)          ThetaResult tagged with its
        → scheduler refresh / topic           committed snapshot version
        birth-death events in StepMetrics

Cappé's online-EM stochastic-approximation argument (PAPERS.md) is what
makes the staleness harmless: serving reads a φ at most `retain`
committed versions behind the trainer, and the trainer's trajectory is
untouched by serving (snapshot reads only — training is bitwise
identical with or without traffic).

    PYTHONPATH=src python -m repro.launch.lifelong --quick
"""
from __future__ import annotations

import argparse
import dataclasses
import threading
import time
from typing import List, Optional, Tuple

import numpy as np

from repro.core import (
    FOEMTrainer,
    LDAConfig,
    ParameterStore,
    ShiftDetector,
    SnapshotPublisher,
)
from repro.core.perplexity import split_heldout_counts
from repro.data import synthetic_lda_corpus
from repro.launch.serve import ServingEngine, TopicServer, TrafficGenerator
from repro.runtime.compile_cache import enable_compile_cache
from repro.sparse import MinibatchStream
from repro.sparse.docword import bucketize


def run_lifelong(
    *,
    workdir: str,
    topics: int = 32,
    vocab: int = 2048,
    docs: int = 512,
    minibatch: int = 64,
    steps: int = 12,
    publish_every: int = 4,
    retain: int = 2,
    requests: int = 128,
    qps: float = 200.0,
    pace: bool = False,
    doc_len: Tuple[int, int] = (8, 48),
    max_batch: int = 32,
    max_delay_ms: float = 5.0,
    fit_sweeps: int = 20,
    hot_rows: int = 256,
    phi_dtype: str = "float32",
    buffer_rows: int = 0,
    seed: int = 0,
    prewarm: bool = True,
    wave_gap_s: float = 0.05,
) -> dict:
    """Run the end-to-end lifelong scenario and return its report dict.

    The trainer consumes an endless minibatch stream (``epochs=None``) and
    publishes a committed snapshot every ``publish_every`` steps; the
    engine replays a Zipf/Poisson trace against whichever version is
    newest at each launch.  The report carries the acceptance evidence:
    publish/swap logs, the observed staleness bound, per-request latency
    percentiles, failed/uncommitted-version counts, shift events, and a
    held-out perplexity measured on the final served version.
    """
    cfg = LDAConfig(num_topics=topics, vocab_size=vocab,
                    max_sweeps=fit_sweeps)
    corpus, _ = synthetic_lda_corpus(
        docs, vocab, topics, mean_doc_len=max(doc_len), seed=seed
    )
    store = ParameterStore(workdir, num_topics=topics,
                           vocab_capacity=vocab + 256,
                           buffer_rows=buffer_rows)
    publisher = SnapshotPublisher(store, retain=retain)
    detector = ShiftDetector()
    trainer = FOEMTrainer(
        cfg, store, seed=seed,
        publisher=publisher, publish_every=publish_every,
        shift_detector=detector,
    )
    # version 1 before any traffic: the server always has a committed φ to
    # pin, even if the first cadence publish hasn't happened yet
    publisher.publish()

    server = TopicServer(store, cfg, fit_sweeps=fit_sweeps, rel_tol=0.0,
                         check_every=max(fit_sweeps, 1),
                         vocab_pad=max(256, min(vocab, 1024)),
                         phi_dtype=phi_dtype, hot_rows=hot_rows)
    server.subscribe(publisher)

    gen = TrafficGenerator(vocab, doc_len=doc_len, seed=seed + 1)
    trace = gen.trace([(qps, requests)])

    train_errors: List[BaseException] = []
    stream = iter(MinibatchStream(corpus, minibatch, seed=seed, epochs=None))
    # step 1 runs synchronously before traffic opens: it pays the trainer's
    # one-off jit compile, so the serving window overlaps actual training
    # steps (and their publishes) instead of a long silent compile
    trainer.step(next(stream))

    def train_loop() -> None:
        try:
            trainer.fit_stream(stream, max_steps=max(steps - 1, 0))
        except BaseException as e:  # surfaced by the driver, never silent
            train_errors.append(e)

    t_start = time.perf_counter()
    max_len = int(np.ceil(max(doc_len) / 16) * 16)
    failed = 0
    served_versions: List[int] = []
    with ServingEngine(server, max_batch=max_batch,
                       max_delay_ms=max_delay_ms,
                       max_len=max_len, seed=seed) as eng:
        if prewarm:
            eng.prewarm()
        th = threading.Thread(target=train_loop, name="lifelong-trainer")
        th.start()
        # traffic must SPAN the publishes (that is the scenario): keep
        # replaying the trace in waves until the trainer finishes, so the
        # latency percentiles cover hot-swaps, not just the first version
        n_submitted = 0
        waves = 0
        while True:
            futs = TrafficGenerator.replay(trace, eng.submit, pace=pace)
            n_submitted += len(futs)
            for f in futs:
                try:
                    theta = f.result(timeout=300.0)
                    served_versions.append(int(getattr(theta, "version", -1)))
                except Exception:
                    failed += 1
            waves += 1
            # the trainer terminates after `steps` steps, so this loop does
            # too; the cap is a backstop against a wedged trainer thread
            if not th.is_alive() or waves >= 1000:
                break
            # yield between waves: an unthrottled closed loop starves the
            # trainer thread of the GIL and the shared CPU device, turning
            # a seconds-long training run into minutes
            time.sleep(wave_gap_s)
        th.join()
        server.refresh()                 # pick up the final publish
        eng.drain()
        m = eng.metrics()
        recompiled = False if not prewarm else (
            eng.compile_count() > eng.prewarm()
        )
        batch_log = list(eng.batch_log)
    if train_errors:
        raise train_errors[0]

    committed = {rec["version"] for rec in publisher.publish_log}
    uncommitted = sorted(set(served_versions) - committed)
    stale = [
        b["published_version"] - b["version"]
        for b in batch_log
        if b.get("version", -1) >= 0 and b.get("published_version", -1) >= 0
    ]

    # held-out perplexity on the final served version (eq. 21): fit θ̂ on
    # 80% of each doc's tokens, score the held-out 20% in the same launch
    ev_rng = np.random.default_rng(seed + 2)
    n_ev = min(64, corpus.num_docs)
    w, c = bucketize(corpus, list(range(n_ev)), pad_multiple=16)
    est, ev = split_heldout_counts(c, ev_rng)
    _, heldout_ppl = server.evaluate(w, est, ev)

    report = {
        "steps": steps,
        "train_steps": len(trainer.history),
        "publishes": len(publisher.publish_log),
        "publish_log": publisher.publish_log,
        "swap_log": server.swap_log,
        "swap_seconds_max": (
            max(s["seconds"] for s in server.swap_log)
            if server.swap_log else 0.0
        ),
        "staleness_versions_max": int(max(stale)) if stale else 0,
        "requests": n_submitted,
        "traffic_waves": waves,
        "failed_requests": failed,
        "uncommitted_versions": uncommitted,
        "served_version_min": min(served_versions) if served_versions else -1,
        "served_version_max": max(served_versions) if served_versions else -1,
        "p50_ms": m.get("p50_ms", 0.0),
        "p99_ms": m.get("p99_ms", 0.0),
        "mean_fill": m.get("mean_fill", 0.0),
        "recompiled": bool(recompiled),
        "heldout_ppl": float(heldout_ppl),
        "shift_events": [dataclasses.asdict(e) for e in detector.events],
        "wall_seconds": time.perf_counter() - t_start,
    }
    return report


def main(argv: Optional[List[str]] = None) -> dict:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workdir", default="/tmp/repro_lifelong")
    ap.add_argument("--topics", type=int, default=32)
    ap.add_argument("--vocab", type=int, default=2048)
    ap.add_argument("--docs", type=int, default=512)
    ap.add_argument("--minibatch", type=int, default=64)
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--publish-every", type=int, default=4)
    ap.add_argument("--retain", type=int, default=2)
    ap.add_argument("--requests", type=int, default=128)
    ap.add_argument("--qps", type=float, default=200.0)
    ap.add_argument("--pace", action="store_true",
                    help="honour trace arrival timestamps")
    ap.add_argument("--max-batch", type=int, default=32)
    ap.add_argument("--max-delay-ms", type=float, default=5.0)
    ap.add_argument("--fit-sweeps", type=int, default=20)
    ap.add_argument("--hot-rows", type=int, default=256)
    ap.add_argument("--phi-dtype", default="float32",
                    choices=("float32", "bfloat16", "int8"))
    ap.add_argument("--buffer-rows", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--quick", action="store_true",
                    help="tiny CI-smoke cell instead of the defaults")
    args = ap.parse_args(argv)
    kw = dict(
        workdir=args.workdir, topics=args.topics, vocab=args.vocab,
        docs=args.docs, minibatch=args.minibatch, steps=args.steps,
        publish_every=args.publish_every, retain=args.retain,
        requests=args.requests, qps=args.qps, pace=args.pace,
        max_batch=args.max_batch, max_delay_ms=args.max_delay_ms,
        fit_sweeps=args.fit_sweeps, hot_rows=args.hot_rows,
        phi_dtype=args.phi_dtype, buffer_rows=args.buffer_rows,
        seed=args.seed,
    )
    if args.quick:
        # minibatch == docs keeps W_s identical across steps, so the
        # trainer compiles once (varying unique-vocab counts would
        # otherwise recompile the step fn every minibatch)
        kw.update(topics=16, vocab=512, docs=128, minibatch=128, steps=6,
                  publish_every=2, requests=48, doc_len=(8, 24),
                  max_batch=16, fit_sweeps=10, hot_rows=64)
    report = run_lifelong(**kw)
    print(f"lifelong: {report['train_steps']} train steps, "
          f"{report['publishes']} publishes, "
          f"{report['requests']} requests "
          f"({report['failed_requests']} failed)")
    print(f"  served versions v{report['served_version_min']}"
          f"..v{report['served_version_max']} "
          f"(staleness ≤ {report['staleness_versions_max']} versions, "
          f"uncommitted: {report['uncommitted_versions'] or 'none'})")
    print(f"  swap ≤ {report['swap_seconds_max']*1e3:.2f}ms  "
          f"p50 {report['p50_ms']:.1f}ms  p99 {report['p99_ms']:.1f}ms  "
          f"held-out ppl {report['heldout_ppl']:.1f}")
    if report["shift_events"]:
        kinds = [e["kind"] for e in report["shift_events"]]
        print(f"  shift events: {kinds}")
    return report


if __name__ == "__main__":
    main()
