"""A training cell: ``FOEMTrainer.fit_stream`` with prefetch on, over a
cycled stream of LDA minibatches, through the program's normal path
(``ParameterStore`` → ``foem_minibatch`` → ``ops.sweep``).

One ``fit_stream`` call runs set-up and window alike.  Its first steps are
set-up: they compile every W_s bucket the cycle holds (the cycle is ordered
so the first minibatches cover all its buckets) and are the steps the
reference follows.  The window then runs whole steps for ``seconds``.  A
step that ends after the window is not counted; the next step boundary ends
the call (the store is thrown away, so its end-of-stream flush is skipped).
"""
from __future__ import annotations

import dataclasses
import os
import shutil
import tempfile
import time
from typing import Dict, List

import numpy as np

from foembench import checks, traffic, work
from foembench.tracing import Capture


class WindowClosed(Exception):
    """Raised from the step callback at the first boundary past the window."""


def lda_config(cfg: dict):
    from repro.core import LDAConfig

    return LDAConfig(
        num_topics=int(cfg["num_topics"]), vocab_size=int(cfg["vocab_size"]),
        alpha_m1=float(cfg["alpha_m1"]), beta_m1=float(cfg["beta_m1"]),
        max_sweeps=int(cfg["max_sweeps"]),
        ppl_check_every=int(cfg["ppl_check_every"]),
        ppl_rel_tol=float(cfg["ppl_rel_tol"]),
        warmup_sweeps=int(cfg["warmup_sweeps"]),
        active_topics=int(cfg["active_topics"]),
    )


def bucket_of(n_words: int) -> int:
    from repro.sparse.docword import VOCAB_BUCKET

    return -(-n_words // VOCAB_BUCKET) * VOCAB_BUCKET


def minibatches(corpus: traffic.Corpus, cfg: dict, seed: int) -> List:
    """The cycle: the program's ``MinibatchStream`` over the corpus, one
    epoch shuffled by ``seed``, with one minibatch of each W_s bucket
    first."""
    from repro.sparse import MinibatchStream
    from repro.sparse.docword import DocWordMatrix

    tr = corpus.train
    mat = DocWordMatrix(indptr=tr.indptr, word_ids=tr.words,
                        counts=tr.counts, vocab_size=int(cfg["vocab_size"]))
    mbs = list(MinibatchStream(mat, int(cfg["minibatch_docs"]),
                               bucket_len=int(cfg["bucket_len"]),
                               seed=seed % (1 << 32), epochs=1))
    first, rest, seen = [], [], set()
    for mb in mbs:
        b = bucket_of(len(mb.local_vocab))
        (rest if b in seen else first).append(mb)
        seen.add(b)
    return first + rest


class Feed:
    """Cycles the minibatches forever; remembers what it served, in order
    (the trainer consumes it in order, so served[s - 1] is step s)."""

    def __init__(self, mbs: List):
        self.mbs = mbs
        self.served: List[int] = []

    def __iter__(self):
        return self

    def __next__(self):
        i = len(self.served) % len(self.mbs)
        self.served.append(i)
        return self.mbs[i]


@dataclasses.dataclass
class StepRecord:
    metrics: object      # the program's StepMetrics
    end: float           # perf_counter at the step's callback
    mb: object           # its Minibatch


def step_work(rec: StepRecord, cfg: dict) -> work.Work:
    mb = rec.mb
    return work.minibatch(
        int((mb.counts > 0).sum()), len(mb.local_vocab), mb.num_docs,
        topics=int(cfg["num_topics"]), active=int(cfg["active_topics"]),
        sweeps=int(rec.metrics.sweeps), warmup=int(cfg["warmup_sweeps"]))


def run(cell, env, seed: int, seconds: float, trace: bool, *,
        control: bool = False, fault=None, window_on: bool = True) -> Dict:
    """One run; ``window_on=False`` (the limit readings) runs only the
    checked steps and the comparison, and ``control=True`` (implies it)
    puts the reference computed in bfloat16 in the program's place."""
    window_on = window_on and not control
    cfg, mix = cell.config, cell.traffic
    t_setup = time.perf_counter()
    import jax

    from repro.core import FOEMTrainer, ParameterStore
    from repro.kernels import ops as kops

    corpus = traffic.lda_corpus(cfg, mix, seed)
    mbs = minibatches(corpus, cfg, seed)
    buckets = sorted({bucket_of(len(mb.local_vocab)) for mb in mbs})
    n_check = int(mix["checked_steps"])
    setup_steps = max(n_check, len(buckets))
    lda = lda_config(cfg)
    trainer_seed = seed % (1 << 31)
    work_dir = tempfile.mkdtemp(prefix="foembench-store-")
    store = ParameterStore(work_dir, num_topics=lda.K,
                           vocab_capacity=lda.W,
                           buffer_rows=int(cfg["buffer_rows"]))
    trainer = FOEMTrainer(lda, store, seed=trainer_seed, prefetch_depth=1)
    if fault is not None:
        fault(trainer)
    feed = Feed(mbs)
    view = np.unique(np.concatenate(
        [mbs[i].local_vocab for i in range(min(n_check, len(mbs)))]))
    snaps = [(np.zeros((len(view), lda.K), np.float32),
              np.zeros((lda.K,), np.float64))]
    records: List[StepRecord] = []
    window = {}
    cap = Capture(os.path.join(env.out_dir, "trace")) if trace else None
    trace_span = {}
    dispatch_mark = [-1]
    counter = env.counter

    def on_step(m):
        now = time.perf_counter()
        if "t0" in window and now > window["t0"] + seconds:
            counter.armed = False
            raise WindowClosed          # this step ended after the window
        records.append(StepRecord(m, now, mbs[feed.served[m.step - 1]]))
        if m.step <= n_check:
            snaps.append((store.fetch_rows(view, promote=False),
                          store.phi_k.copy()))
        if m.step == setup_steps:
            window["setup_s"] = now - t_setup
            window["t0"] = now
            window["first"] = len(records)
            window["cache"] = sum(fn._cache_size()
                                  for fn in trainer._jit_cache.values())
            log = kops.dispatch_log()
            dispatch_mark[0] = log[-1].seq if log else -1
            counter.armed = True
            return
        if "t0" in window and cap is not None:
            elapsed = now - window["t0"]
            if not trace_span and elapsed >= 0.25 * seconds:
                trace_span["first"] = len(records)
                cap.start()
            elif ("first" in trace_span and "last" not in trace_span
                  and (len(records) - trace_span["first"] >= 2)
                  and (elapsed >= 0.25 * seconds + float(mix["trace_seconds"])
                       or elapsed >= 0.9 * seconds)):
                cap.stop()
                trace_span["last"] = len(records)

    env.say(f"{cell.name}: K={lda.K} W={lda.W} D_s={cfg['minibatch_docs']} "
        f"L={cfg['bucket_len']} A={lda.active_topics}; cycle of {len(mbs)} "
        f"minibatches, W_s buckets {buckets}; {setup_steps} set-up steps")
    try:
        trainer.fit_stream(feed, max_steps=None if window_on else n_check,
                           callback=on_step)
    except WindowClosed:
        pass
    finally:
        counter.armed = False
        if cap is not None and "first" in trace_span and "last" not in trace_span:
            cap.stop()
            trace_span["last"] = len(records)
    out = {"devices": env.devices, "notes": []}
    check_recs = records[:n_check]
    if window_on:
        win = records[window["first"]:]
        if not win:
            raise RuntimeError("no step completed inside the window; "
                               "raise --seconds")
        wall = win[-1].end - window["t0"]
        tokens = sum(float(r.mb.counts.sum()) for r in win)
        new_dispatch = kops.dispatch_log(since=dispatch_mark[0])
        cache_after = sum(fn._cache_size()
                          for fn in trainer._jit_cache.values())
        sweeps_log = [d for d in kops.dispatch_log() if d.entry == "sweep"]
        out["notes"] += [
            "sweep dispatch: " + ", ".join(sorted({str(d) for d in sweeps_log})),
            f"compiles inside the window: {counter.lowered} programs lowered, "
            f"{counter.compiled} compiled by XLA, {len(new_dispatch)} dispatch "
            f"decisions traced, step cache {window['cache']} -> {cache_after}",
            "W_s buckets hit in the window: " + str(sorted(
                {bucket_of(len(r.mb.local_vocab)) for r in win})),
            f"window: {len(win)} steps, {tokens:.0f} tokens in {wall:.3f} s; "
            f"sweeps per step {[int(r.metrics.sweeps) for r in win][:12]}...",
        ]
        out["attempted"] = len(win)
        out["failed"] = 0
        out["e2e"] = {"setup_s": window["setup_s"],
                      "train_tokens_per_s": tokens / wall}
        out["memory_peak_bytes"] = env.memory_peak()
        out["ctx"] = {
            "kind": "train", "config": cfg, "steps": [r.metrics for r in win],
            "window_s": wall, "sweep_paths": {d.path for d in sweeps_log},
            "work": [step_work(r, cfg) for r in win],
        }
        if cap is not None and "last" in trace_span:
            red = cap.reduce()
            traced = records[trace_span["first"]:trace_span["last"]]
            out["ctx"]["trace"] = red
            out["ctx"]["trace_work"] = [step_work(r, cfg) for r in traced]
            out["notes"].append(
                f"trace: {len(traced)} steps, {red.window_s:.3f} s, "
                f"{len(red.device_ops)} device operations")
        # held-out perplexity on the φ̂ the window left
        held = [corpus.heldout.doc(i) for i in range(corpus.heldout.n)]
        vocab_h = np.unique(np.concatenate([w for w, _ in held]))
        rows_h = store.fetch_rows(vocab_h, promote=False)
        out["e2e"]["train_heldout_ppl"] = cell.reference.heldout_perplexity(
            held, {int(w): i for i, w in enumerate(vocab_h)}, rows_h,
            store.phi_k.copy(), vocab=lda.W, alpha_m1=lda.alpha_m1,
            beta_m1=lda.beta_m1, split_seed=int(mix["topics"]["base_seed"]))
    del trainer
    shutil.rmtree(work_dir, ignore_errors=True)

    # --- the reference follows the first steps -----------------------------
    key = jax.random.PRNGKey(trainer_seed)
    steps = []
    for r in check_recs:
        key, sub = jax.random.split(key)
        steps.append(cell.reference.StepInput(
            sub, np.searchsorted(view, r.mb.local_vocab), r.mb.local_word_ids,
            r.mb.counts, int(r.metrics.sweeps)))
    kw = dict(vocab=lda.W, alpha_m1=lda.alpha_m1, beta_m1=lda.beta_m1,
              warmup=lda.warmup_sweeps, check_every=lda.ppl_check_every,
              active=lda.active_topics)
    t_ref = time.perf_counter()
    ref = cell.reference.foem_steps(steps, len(view), lda.K, **kw)
    if control:
        import jax.numpy as jnp

        low = cell.reference.foem_steps(steps, len(view), lda.K,
                                        dtype=jnp.bfloat16, **kw)
        snaps = snaps[:1] + [(p, k.astype(np.float64)) for p, k, _ in low]
        ppl = [q for _, _, q in low]
    else:
        ppl = [float(r.metrics.train_ppl) for r in check_recs]
    out["checks"] = checks.training(snaps, ppl, ref, steps,
                                    cfg["limits"]["train"])
    out["notes"].append(
        f"reference: {len(steps)} steps in {time.perf_counter() - t_ref:.1f} s"
        f" over {len(view)} words; program train ppl {ppl}, reference "
        f"{[q for _, _, q in ref]}")
    return out
