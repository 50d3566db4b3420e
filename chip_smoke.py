#!/usr/bin/env python3
"""Drive the FOEM trainer and the topic server once on a TPU and check them.

    python chip_smoke.py              # one chip: phases (a), (b), (c)
    python chip_smoke.py --chips 4    # four chips: the cross-chip paths only

Run it from the root of a checkout.  Everything runs in this one process
(a process that has touched JAX holds the chips) and stops with a non-zero
exit on the first failed check, or when JAX finds no TPU.

One chip:
  (a) streamed FOEM training (``FOEMTrainer.fit_stream``, prefetch on) at
      the reference cell D_s=256, L=64, K=128, W=8192, A=16; the sweeps
      must take the Pallas kernels, and one dense and one scheduled sweep
      must match the portable mirror (``use_pallas=False``) to 1e-4;
  (b) training at the paper's ``stream_1k`` widths (K=10,000, W=141,043,
      L=128) with D_s cut to 256; the (W_s, K) block cannot fit VMEM, so
      the sweep dispatch must report "portable: VMEM";
  (c) serving (``TopicServer`` + ``ServingEngine``) on the store from (a)
      with f32 and int8 φ: prewarm, a few hundred Zipf requests, no new
      compilation, θ rows summing to 1, ``theta_sweep`` as a kernel, and
      one batch matching ``use_pallas=False``.

Four chips: ``foem_step_sharded`` (two-phase kernels on a 1x4 mesh), one
dense and one scheduled two-phase sweep on the mesh against the portable
mirror (to 1e-4), and the trained φ̂ and train ppl against single-device
``foem_step``; and a 4-replica thread ``ReplicaPool``, one chip per
replica, whose θ must equal a single ``ServingEngine``'s bitwise.

The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time
from types import SimpleNamespace

ROOT = os.path.dirname(os.path.abspath(__file__))
SEED = 0
PARITY_TOL = 1e-4


class SmokeFailure(RuntimeError):
    """A check of the smoke failed."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(msg, flush=True)


def rel_err(got, want) -> float:
    """max |got - want| / max |want| (norm-wise, robust near zeros)."""
    import numpy as np

    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    scale = max(float(np.abs(want).max()), 1e-30)
    return float(np.abs(got - want).max()) / scale


def dispatch_since(mark: int, entry: str):
    from repro.kernels import ops as kops

    return [d for d in kops.dispatch_log(since=mark) if d.entry == entry]


def dispatch_mark() -> int:
    from repro.kernels import ops as kops

    log = kops.dispatch_log()
    return log[-1].seq if log else -1


def summarize(decisions) -> str:
    return ", ".join(sorted({str(d) for d in decisions})) or "none traced"


# ---------------------------------------------------------------------------
# (a) training at the reference cell
# ---------------------------------------------------------------------------

REF = dict(D=256, L=64, K=128, W=8192, A=16)
#: D_s of phase (b), cut from stream_1k's 1024 so μ fits the chip
PAPER_DOCS = 256


def phase_train_reference(work: str):
    import jax
    import numpy as np

    from repro.core import FOEMTrainer, LDAConfig, ParameterStore
    from repro.data import synthetic_lda_corpus
    from repro.sparse import MinibatchStream

    D, L, K, W, A = (REF[k] for k in "DLKWA")
    cfg = LDAConfig(num_topics=K, vocab_size=W, active_topics=A)
    corpus, _ = synthetic_lda_corpus(6 * D, W, 32, mean_doc_len=48,
                                     seed=SEED)
    store = ParameterStore(os.path.join(work, "ref_store"), num_topics=K,
                           vocab_capacity=W, buffer_rows=W)
    trainer = FOEMTrainer(cfg, store, seed=SEED, prefetch_depth=1)
    stream = MinibatchStream(corpus, D, bucket_len=L, seed=SEED, epochs=None)
    mark = dispatch_mark()
    t0 = time.perf_counter()
    hist = trainer.fit_stream(
        iter(stream), max_steps=4,
        callback=lambda m: say(
            f"  (a) step {m.step}: sweeps={m.sweeps} "
            f"train_ppl={m.train_ppl:.4f} {m.seconds:.2f}s "
            f"prefetch_hit={m.prefetch_hit}"),
    )
    sweeps = dispatch_since(mark, "sweep")
    say(f"(a) trained {len(hist)} minibatches at D_s={D} L={L} K={K} W={W} "
        f"A={A} in {time.perf_counter() - t0:.1f}s; sweep dispatch: "
        f"{summarize(sweeps)}")
    check(len(hist) == 4, "phase (a) ran fewer than 4 minibatches")
    check(all(np.isfinite(m.train_ppl) for m in hist),
          "phase (a) train_ppl is not finite")
    check(sweeps and all(d.path == "pallas" for d in sweeps),
          f"phase (a) sweeps did not all take the Pallas kernels: "
          f"{summarize(sweeps)}")
    compiles = [fn._cache_size() for fn in trainer._jit_cache.values()]
    say(f"(a) step compiles per W_s bucket: {compiles}")
    check(all(n == 1 for n in compiles),
          f"phase (a) recompiled within a W_s bucket: {compiles}")

    # one dense and one scheduled sweep against the portable mirror
    mb = next(iter(MinibatchStream(corpus, D, bucket_len=L, seed=SEED + 1)))
    errs = sweep_parity(cfg, store, mb, jax.random.PRNGKey(SEED + 1))
    for name, err in errs.items():
        say(f"(a) {name} sweep vs portable mirror: max rel err {err:.3e}")
        check(err <= PARITY_TOL,
              f"phase (a) {name} sweep parity {err:.3e} > {PARITY_TOL}")
    return cfg, store


def sweep_parity(cfg, store, mb, key):
    """Max relative error of the kernel sweeps against ``use_pallas=False``
    on the same inputs: one dense and one scheduled sweep."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import em
    from repro.core import scheduling as sched_lib
    from repro.core.types import uniform_responsibilities
    from repro.kernels import ops as kops
    from repro.sparse.docword import pad_vocab_rows

    wid = jnp.asarray(mb.local_word_ids)
    cnt = jnp.asarray(mb.counts)
    rows = jnp.asarray(pad_vocab_rows(store.fetch_rows(mb.local_vocab)))
    D, L = wid.shape
    mu = uniform_responsibilities(key, (D, L, cfg.K))
    theta = em.fold_theta(mu, cnt)
    d_wk, d_k = em.fold_phi(mu, cnt, wid, rows.shape[0])
    phi = rows + d_wk
    ptot = jnp.asarray(store.phi_k, jnp.float32) + d_k
    kw = dict(alpha_m1=cfg.alpha_m1, beta_m1=cfg.beta_m1,
              wb=cfg.W * cfg.beta_m1, compute_loglik=True)

    def run(use_pallas):
        f = jax.jit(lambda *a: kops.sweep(*a, **kw, use_pallas=use_pallas))
        return f(wid, cnt, mu, theta, phi, ptot)

    def err(a, b):
        fields = ("mu", "theta", "phi_wk", "phi_k", "residual")
        e = max(rel_err(getattr(a, f), getattr(b, f)) for f in fields)
        return max(e, rel_err(a.loglik, b.loglik))

    mark = dispatch_mark()
    dense_k, dense_p = run(None), run(False)
    sched = sched_lib.residuals_from_sweep(dense_p.residual, wid,
                                           rows.shape[0])
    word_topics = sched_lib.select_active_topics(sched, cfg.active_topics)
    extra = dict(word_topics=word_topics, token_active=cnt > 0)
    args = (wid, cnt, dense_p.mu, dense_p.theta, dense_p.phi_wk,
            dense_p.phi_k)
    sched_k = jax.jit(lambda *a: kops.sweep(*a, **kw, **extra))(*args)
    sched_p = jax.jit(lambda *a: kops.sweep(*a, **kw, **extra,
                                            use_pallas=False))(*args)
    kernel_runs = [d for d in dispatch_since(mark, "sweep")
                   if d.path == "pallas"]
    check(len(kernel_runs) == 2,
          "parity sweeps did not take the kernels: "
          + summarize(dispatch_since(mark, "sweep")))
    out = {"dense": err(dense_k, dense_p), "scheduled": err(sched_k, sched_p)}
    check(all(np.isfinite(v) for v in out.values()), "parity error not finite")
    return out


# ---------------------------------------------------------------------------
# (b) training at the paper's stream_1k widths
# ---------------------------------------------------------------------------

def phase_train_paper_widths(work: str) -> None:
    import numpy as np

    from repro.configs.foem_lda import LDA_SHAPES, lda_config
    from repro.core import FOEMTrainer, ParameterStore
    from repro.data import synthetic_lda_corpus
    from repro.sparse import MinibatchStream

    shape = LDA_SHAPES[0]                      # stream_1k
    D = PAPER_DOCS
    cfg = lda_config(shape)
    say(f"(b) {shape.name}: K={shape.num_topics} W={shape.vocab_size} "
        f"L={shape.bucket_len}; D_s cut {shape.minibatch_docs} -> {D} "
        "(the dense (D, L, K) f32 μ and residual are 5.2 GB each at "
        "1024, 1.3 GB at 256)")
    t0 = time.perf_counter()
    corpus, _ = synthetic_lda_corpus(3 * D, shape.vocab_size, 16,
                                     mean_doc_len=160, seed=SEED)
    say(f"(b) corpus of {corpus.num_docs} docs generated in "
        f"{time.perf_counter() - t0:.1f}s")
    store = ParameterStore(os.path.join(work, "paper_store"),
                           num_topics=cfg.K, vocab_capacity=cfg.W)
    trainer = FOEMTrainer(cfg, store, seed=SEED, prefetch_depth=1)
    stream = MinibatchStream(corpus, D, bucket_len=shape.bucket_len,
                             seed=SEED, epochs=None)
    mark = dispatch_mark()
    t0 = time.perf_counter()
    hist = trainer.fit_stream(
        iter(stream), max_steps=2,
        callback=lambda m: say(
            f"  (b) step {m.step}: sweeps={m.sweeps} "
            f"train_ppl={m.train_ppl:.4f} {m.seconds:.2f}s"),
    )
    sweeps = dispatch_since(mark, "sweep")
    say(f"(b) trained {len(hist)} minibatches in "
        f"{time.perf_counter() - t0:.1f}s; sweep dispatch: "
        f"{summarize(sweeps)}")
    check(len(hist) == 2, "phase (b) ran fewer than 2 minibatches")
    check(all(np.isfinite(m.train_ppl) for m in hist),
          "phase (b) train_ppl is not finite")
    check(sweeps and all(d.path == "portable" and d.reason == "VMEM"
                         for d in sweeps),
          f"phase (b) dispatch is not 'portable: VMEM': {summarize(sweeps)}")


# ---------------------------------------------------------------------------
# (c) serving at the reference cell
# ---------------------------------------------------------------------------

def phase_serve(cfg, store) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.serve import (
        ServingEngine,
        TopicServer,
        TrafficGenerator,
        pad_batch,
    )

    L, max_batch, vocab_pad = REF["L"], REF["D"], 2048
    gen = TrafficGenerator(cfg.W, seed=SEED + 7, doc_len=(16, L))
    trace = gen.trace([(500.0, 384)])
    for dtype in ("float32", "int8"):
        server = TopicServer(store, cfg, fit_sweeps=20, check_every=10,
                             vocab_pad=vocab_pad, phi_dtype=dtype)
        mark = dispatch_mark()
        t0 = time.perf_counter()
        with ServingEngine(server, max_batch=max_batch, bucket_multiple=L,
                           max_len=L, max_delay_ms=20.0,
                           seed=SEED) as eng:
            compiled = eng.prewarm()
            t1 = time.perf_counter()
            futs = TrafficGenerator.replay(trace, eng.submit, pace=False)
            thetas = np.stack([np.asarray(f.result(timeout=600))
                               for f in futs])
            eng.drain()
            t2 = time.perf_counter()
            after = eng.compile_count()
            m = eng.metrics()
        infers = dispatch_since(mark, "infer")
        say(f"(c) {dtype}: prewarm compiled {compiled} traces in "
            f"{t1 - t0:.1f}s; served {len(thetas)} requests in "
            f"{m['batches']} batches in {t2 - t1:.2f}s; infer dispatch: "
            f"{summarize(infers)}")
        check(after == compiled,
              f"phase (c) {dtype}: compile_count moved {compiled} -> {after}")
        row_err = float(np.abs(thetas.sum(-1) - 1.0).max())
        say(f"(c) {dtype}: max |sum θ - 1| = {row_err:.2e}")
        check(row_err <= 1e-4, f"phase (c) {dtype}: θ rows do not sum to 1")
        check(infers and all(d.path == "pallas" for d in infers),
              f"phase (c) {dtype}: theta_sweep did not run as a kernel: "
              f"{summarize(infers)}")

        # one batch against the portable mirror, fixed sweeps both sides
        reqs = [SimpleNamespace(word_ids=w, counts=c,
                                key=np.asarray([SEED, i], np.uint32))
                for i, (_, w, c) in enumerate(trace[:max_batch])]
        w, c, keys = pad_batch(L, reqs, max_batch)
        got, want = (
            TopicServer(store, cfg, fit_sweeps=20, check_every=10,
                        rel_tol=0.0, vocab_pad=vocab_pad, phi_dtype=dtype,
                        use_pallas=use).infer(w, c, key=jnp.asarray(keys))
            for use in (None, False)
        )
        err = rel_err(got, want)
        say(f"(c) {dtype}: batch of {len(reqs)} vs portable mirror: "
            f"max rel err {err:.3e}")
        check(err <= PARITY_TOL,
              f"phase (c) {dtype} parity {err:.3e} > {PARITY_TOL}")


# ---------------------------------------------------------------------------
# four chips: sharded training and the replica pool
# ---------------------------------------------------------------------------

def phase_sharded():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import LDAConfig
    from repro.core.foem import foem_step
    from repro.core.foem_sharded import foem_step_sharded
    from repro.core.types import GlobalStats, MinibatchData
    from repro.data import synthetic_lda_corpus
    from repro.parallel import compat
    from repro.sparse import MinibatchStream

    D, L, K, W, A = (REF[k] for k in "DLKWA")
    mp = len(jax.devices())
    cfg = LDAConfig(num_topics=K, vocab_size=W, active_topics=A,
                    topk_shards=mp)
    corpus, _ = synthetic_lda_corpus(3 * D, W, 32, mean_doc_len=48,
                                     seed=SEED)
    batches = [
        MinibatchData(jnp.asarray(mb.word_ids), jnp.asarray(mb.counts))
        for _, mb in zip(range(2), MinibatchStream(corpus, D, bucket_len=L,
                                                   seed=SEED))
    ]
    mesh = compat.make_mesh((1, mp), ("data", "model"))
    key = jax.random.PRNGKey(SEED)

    def sharded():
        stats = GlobalStats.zeros(cfg)
        stats = GlobalStats(
            jax.device_put(stats.phi_wk, NamedSharding(mesh, P(None, "model"))),
            jax.device_put(stats.phi_k, NamedSharding(mesh, P("model"))),
            stats.step,
        )
        step = jax.jit(lambda k, b, s: foem_step_sharded(k, b, s, cfg, mesh))
        ppls = []
        for i, b in enumerate(batches):
            stats, ppl = step(jax.random.fold_in(key, i), b, stats)
            ppls.append(float(ppl))
        return np.asarray(stats.phi_wk), np.asarray(stats.phi_k), ppls

    mark = dispatch_mark()
    t0 = time.perf_counter()
    phi_s, phik_s, ppl_s = sharded()
    sweeps = dispatch_since(mark, "sweep")
    say(f"(4a) foem_step_sharded on a 1x{mp} mesh, {len(batches)} "
        f"minibatches in {time.perf_counter() - t0:.1f}s; sweep dispatch: "
        f"{summarize(sweeps)}; train ppl {ppl_s}")
    check(sweeps and all(d.path == "pallas" for d in sweeps),
          f"sharded sweeps did not take the two-phase kernels: "
          f"{summarize(sweeps)}")

    errs = sharded_sweep_parity(cfg, mesh, batches[0], phi_s, phik_s,
                                jax.random.PRNGKey(SEED + 1))
    for name, err in errs.items():
        say(f"(4a) {name} two-phase sweep on the mesh vs portable mirror: "
            f"max rel err {err:.3e}")
        check(err <= PARITY_TOL,
              f"sharded {name} sweep parity {err:.3e} > {PARITY_TOL}")

    stats = GlobalStats.zeros(cfg)
    ppl_1 = []
    for i, b in enumerate(batches):
        stats, _, diag = foem_step(jax.random.fold_in(key, i), b, stats, cfg)
        ppl_1.append(float(diag.final_train_ppl))
    phi_1 = np.asarray(stats.phi_wk)
    e_phi1, e_ppl1 = rel_err(phi_s, phi_1), rel_err(ppl_s, ppl_1)
    say(f"(4a) sharded vs single-device foem_step: φ̂ rel err {e_phi1:.3e}, "
        f"train ppl rel err {e_ppl1:.3e} (single {ppl_1}); the two draw "
        "their initial μ and their active sets differently")
    tokens = float(sum(float(b.counts.sum()) for b in batches))
    check(abs(float(phik_s.sum()) - tokens) <= 1e-3 * tokens,
          "sharded φ̂(k) lost token mass")
    check(e_ppl1 <= 0.1, "sharded train ppl is far from single-device")
    return cfg, phi_1, np.asarray(stats.phi_k)


def sharded_sweep_parity(cfg, mesh, batch, phi, phi_k, key):
    """Max relative error of one two-phase sweep inside ``shard_map`` on
    the kernels (``impl="pallas"``) against the portable two-phase mirror,
    on the same inputs: one dense and one scheduled sweep.

    Whole trajectories are not compared: the active topics are a top-k of
    the residuals, so round-off in one sweep can swap a topic and move
    every later sweep."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.core import em
    from repro.core import scheduling as sched_lib
    from repro.core.types import SweepPlan, uniform_responsibilities
    from repro.kernels import ops as kops
    from repro.parallel import compat

    wid, cnt = batch.word_ids, batch.counts
    D, L = wid.shape
    mp = mesh.shape["model"]
    mu = uniform_responsibilities(key, (D, L, cfg.K))
    theta = em.fold_theta(mu, cnt)
    d_wk, d_k = em.fold_phi(mu, cnt, wid, cfg.W)
    phi = jnp.asarray(phi) + d_wk
    ptot = jnp.asarray(phi_k, jnp.float32) + d_k
    # per-shard active sets from the first sweep's residuals, as FOEM's
    # scheduler picks them after a dense sweep
    r_wk = sched_lib.residuals_from_sweep(
        kops.sweep(wid, cnt, mu, theta, phi, ptot, alpha_m1=cfg.alpha_m1,
                   beta_m1=cfg.beta_m1, wb=cfg.W * cfg.beta_m1,
                   use_pallas=False).residual, wid, cfg.W).r_wk
    kw = dict(alpha_m1=cfg.alpha_m1, beta_m1=cfg.beta_m1,
              wb=cfg.W * cfg.beta_m1, compute_loglik=True)

    def run(impl, scheduled):
        def body(mu, theta, phi, ptot, r_loc):
            extra = {}
            if scheduled:
                s = sched_lib.SchedulerState(r_wk=r_loc, r_w=r_loc.sum(-1))
                extra = dict(
                    word_topics=sched_lib.select_active_topics(
                        s, cfg.active_topics // mp),
                    token_active=cnt > 0,
                )
            r = kops.sweep(wid, cnt, mu, theta, phi, ptot, **kw, **extra,
                           plan=SweepPlan(axis_name="model", impl=impl))
            return r.mu, r.theta, r.phi_wk, r.phi_k, r.residual, r.loglik

        topic = P(None, None, "model")
        return jax.jit(compat.shard_map(
            body, mesh=mesh,
            in_specs=(topic, P(None, "model"), P(None, "model"), P("model"),
                      P(None, "model")),
            out_specs=(topic, P(None, "model"), P(None, "model"), P("model"),
                       topic, P()),
        ))(mu, theta, phi, ptot, r_wk)

    mark = dispatch_mark()
    out = {}
    for name, scheduled in (("dense", False), ("scheduled", True)):
        got, want = run("pallas", scheduled), run("portable", scheduled)
        out[name] = max(rel_err(a, b) for a, b in zip(got, want))
    kernel_runs = [d for d in dispatch_since(mark, "sweep")
                   if d.path == "pallas"]
    check(len(kernel_runs) == 2,
          "sharded parity sweeps did not take the kernels: "
          + summarize(dispatch_since(mark, "sweep")))
    check(all(np.isfinite(v) for v in out.values()), "parity error not finite")
    return out


def phase_replicas(work: str, cfg, phi, phi_k) -> None:
    import jax
    import numpy as np

    from repro.core import ParameterStore
    from repro.launch.replica import ReplicaPool
    from repro.launch.serve import ServingEngine, TopicServer, TrafficGenerator

    n = len(jax.devices())
    store = ParameterStore(os.path.join(work, "pool_store"),
                           num_topics=cfg.K, vocab_capacity=cfg.W)
    store.write_rows(np.arange(cfg.W), phi)
    store.phi_k = np.asarray(phi_k, np.float64)
    store.flush()
    L, max_batch = REF["L"], 64
    gen = TrafficGenerator(cfg.W, seed=SEED + 9, doc_len=(16, L))
    docs = [(w, c) for _, w, c in gen.trace([(500.0, 512)])]
    keys = [np.asarray([SEED, i], np.uint32) for i in range(len(docs))]

    def server():
        return TopicServer(store, cfg, fit_sweeps=20, check_every=10,
                           rel_tol=0.0, vocab_pad=2048)

    dims = dict(max_batch=max_batch, bucket_multiple=L, max_len=L,
                max_delay_ms=20.0, seed=SEED)
    with ServingEngine(server(), **dims) as eng:
        want = [np.asarray(f.result(timeout=600)) for f in
                [eng.submit(w, c, key=k) for (w, c), k in zip(docs, keys)]]
    t0 = time.perf_counter()
    with ReplicaPool(replicas=n, backend="thread",
                     servers=[server() for _ in range(n)], **dims) as pool:
        pool.wait_ready(600)
        got = [np.asarray(f.result(timeout=600)) for f in
               [pool.submit(w, c, key=k) for (w, c), k in zip(docs, keys)]]
        pool.drain()
        m = pool.metrics()
    same = sum(np.array_equal(a, b) for a, b in zip(got, want))
    say(f"(4b) {n} thread replicas served {len(got)} requests in "
        f"{time.perf_counter() - t0:.1f}s, dispatch {m['dispatch']}; "
        f"{same}/{len(got)} θ bitwise equal to one ServingEngine")
    check(same == len(got), "replica θ differ from the single engine")
    check(sum(1 for v in m["dispatch"].values() if v) > 1,
          "the pool used only one replica")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the cross-chip paths on a 4-chip host")
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke.py: no src/repro next to {__file__}; run it from "
              "a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, src)

    from repro.runtime.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke.py: JAX found no TPU (platform "
              f"{devs[0].platform!r}); this smoke runs on the chip only",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke.py: --chips {args.chips} but JAX sees "
              f"{len(devs)} device(s)", file=sys.stderr)
        return 1
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    say(f"devices: {len(devs)} x {devs[0].device_kind}; jax "
        f"{jax.__version__}; compile cache {cache}")

    os.makedirs(os.path.join(ROOT, ".smoke_work"), exist_ok=True)
    work = tempfile.mkdtemp(dir=os.path.join(ROOT, ".smoke_work"))
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            cfg, phi, phi_k = phase_sharded()
            phase_replicas(work, cfg, phi, phi_k)
        else:
            cfg, store = phase_train_reference(work)
            phase_train_paper_widths(work)
            phase_serve(cfg, store)
    except SmokeFailure as e:
        print(f"chip_smoke.py: FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        shutil.rmtree(work, ignore_errors=True)
    say(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
