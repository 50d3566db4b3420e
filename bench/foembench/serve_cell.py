"""A serving cell: an open loop of requests into ``ServingEngine.submit``,
through the program's normal path (``AdmissionRouter`` → ``TopicServer``
row gather → ``ops.infer``), against a frozen φ̂ made from the generator's
topics.

Set-up writes φ̂ into a ``ParameterStore``, builds the engine and prewarms
its (L, W_s) trace grid.  The window sends every request at its due time;
each request's latency runs from its due time to the done-callback of its
future, so a late generator or a queue shows as latency.  A request that
fails, or has not answered a minute after the window closed, is a miss.
"""
from __future__ import annotations

import contextlib
import functools
import os
import shutil
import tempfile
import time
from concurrent.futures import wait
from typing import Dict

import jax
import numpy as np

from foembench import checks, reference, traffic
from foembench.tracing import Capture
from foembench.train_cell import lda_config

#: how long after the window closes the run waits for outstanding answers
ANSWER_WAIT_S = 60.0


def nearest_rank(values: np.ndarray, q: float) -> float:
    """The q-th percentile by nearest rank (a value that was observed)."""
    v = np.sort(np.asarray(values, np.float64))
    return float(v[max(0, int(np.ceil(q / 100.0 * len(v))) - 1)])


def run(cell, env, seed: int, seconds: float, trace: bool, *,
        control: bool = False, fault=None, rate: float = None) -> Dict:
    cfg, mix = cell.config, cell.traffic
    s = cfg["serve"]
    t_setup = time.perf_counter()
    from repro.core import ParameterStore
    from repro.kernels import ops as kops
    from repro.launch.serve import ServingEngine, TopicServer

    lda = lda_config(cfg)
    phi = traffic.topic_word_stats(cfg, mix)
    phi_k = phi.astype(np.float64).sum(0)
    work_dir = tempfile.mkdtemp(prefix="foembench-store-")
    store = ParameterStore(work_dir, num_topics=lda.K, vocab_capacity=lda.W,
                           buffer_rows=int(cfg["buffer_rows"]))
    store.write_rows(np.arange(lda.W), phi)
    store.phi_k = phi_k.copy()
    server = TopicServer(store, lda, fit_sweeps=int(s["fit_sweeps"]),
                         check_every=int(s["check_every"]),
                         rel_tol=float(s["rel_tol"]),
                         vocab_pad=int(s["vocab_pad"]),
                         phi_dtype="bfloat16" if control else s["phi_dtype"])
    if fault is not None:
        fault(server)
    eng = ServingEngine(server, max_batch=int(s["max_batch"]),
                        bucket_multiple=int(s["bucket_multiple"]),
                        max_len=int(s["max_len"]),
                        max_delay_ms=float(s["max_delay_ms"]),
                        seed=seed % (1 << 31))
    out = {"devices": env.devices, "notes": []}
    try:
        compiled = eng.prewarm()
        reqs = traffic.open_loop(cfg, mix, seed, seconds, rate)
        n = int(np.searchsorted(reqs.due, seconds))
        log = kops.dispatch_log()
        mark = log[-1].seq if log else -1
        setup_s = time.perf_counter() - t_setup
        res = _window(eng, reqs, n, seconds, env, trace,
                      float(mix["trace_seconds"]))
        env.counter.armed = False
        new_dispatch = kops.dispatch_log(since=mark)
        infers = [d for d in kops.dispatch_log() if d.entry == "infer"]
        out["memory_peak_bytes"] = env.memory_peak()
    finally:
        env.counter.armed = False
        eng.close()
    lat, done, ok = res["lat"], res["done"], res["ok"]
    in_window = ok & (done <= seconds)
    failed = int((~ok).sum())
    late = res["sent"] - reqs.due[:n]
    log_w = res["batch_log"]
    out["notes"] += [
        "infer dispatch: " + ", ".join(sorted({str(d) for d in infers})),
        f"compiles inside the window: {env.counter.lowered} programs lowered,"
        f" {env.counter.compiled} compiled by XLA, {len(new_dispatch)} "
        f"dispatch decisions traced, engine traces {compiled} -> "
        f"{eng.compile_count()}",
        f"offered {reqs.rate:.1f} docs/s: {n} requests due in the window, "
        f"{int(in_window.sum())} answered in it, {failed} failed or "
        f"unanswered; {len(log_w)} launches, mean fill "
        f"{np.mean([b['filled'] for b in log_w]) if log_w else 0:.1f}",
        f"generator lateness: max {late.max() * 1e3:.3f} ms, p99 "
        f"{nearest_rank(late, 99) * 1e3:.3f} ms",
        f"latency from due time: p50 {nearest_rank(lat, 50) * 1e3:.3f} ms, "
        f"p99 {nearest_rank(lat, 99) * 1e3:.3f} ms over {n} requests",
    ]
    out["attempted"] = n
    out["failed"] = failed
    out["e2e"] = {"setup_s": setup_s,
                  "serve_p99_ms": nearest_rank(lat, 99) * 1e3,
                  "serve_docs_per_s": float(in_window.sum()) / seconds}
    out["ctx"] = {"kind": "serve", "config": cfg, "batch_log": log_w,
                  "window_s": seconds}
    if res.get("trace") is not None:
        out["ctx"]["trace"] = res["trace"]
        out["ctx"]["trace_launches"] = res["trace_launches"]
        out["notes"].append(
            f"trace: {res['trace_launches']} launches, "
            f"{res['trace'].window_s:.3f} s, "
            f"{len(res['trace'].device_ops)} device operations")

    # --- the reference answers a seeded sample of the window's requests ---
    answered = np.flatnonzero(ok)
    rng = np.random.default_rng(seed)
    k = min(int(mix["checked_requests"]), len(answered))
    sizes = np.diff(reqs.docs.indptr)[answered]
    pick = set(rng.choice(answered, size=max(0, k - 1), replace=False).tolist())
    if len(answered):
        pick.add(int(answered[np.argmax(sizes)]))
    pick = sorted(pick)
    t_ref = time.perf_counter()
    theta = np.stack([np.asarray(res["futures"][i].result()) for i in pick])
    theta_ref = reference.infer_theta(
        [reqs.docs.doc(i) for i in pick], reqs.keys[pick],
        reference.normalized_phi(phi, phi_k, lda.W, lda.beta_m1),
        alpha_m1=lda.alpha_m1, sweeps=int(s["fit_sweeps"]),
        bucket=int(s["bucket_multiple"]))
    out["checks"] = checks.serving(theta, theta_ref, cfg["limits"]["serve"])
    out["notes"].append(
        f"reference: {len(pick)} requests (longest {int(sizes.max()) if len(sizes) else 0}"
        f" words) in {time.perf_counter() - t_ref:.1f} s")
    shutil.rmtree(work_dir, ignore_errors=True)
    return out


def _window(eng, reqs, n: int, seconds: float, env, trace: bool,
            trace_seconds: float) -> Dict:
    """Send requests [0, n) at their due times; return latencies etc."""
    done = np.full(n, np.inf)
    ok = np.zeros(n, bool)
    sent = np.zeros(n)
    futures = [None] * n
    cap = (Capture(os.path.join(env.out_dir, "trace"), python=False)
           if trace else None)
    span = (jax.profiler.TraceAnnotation if trace
            else lambda name: contextlib.nullcontext())
    t_trace = (0.25 * seconds, min(0.9 * seconds,
                                   0.25 * seconds + trace_seconds))
    tracing = {"on": False, "off": False}
    log_start = len(eng.batch_log)

    def stamp(i, fut):
        done[i] = time.perf_counter() - t0
        ok[i] = fut.exception() is None

    t0 = time.perf_counter() + 0.05
    env.counter.armed = True
    i = 0
    while i < n:
        now = time.perf_counter() - t0
        if cap is not None and not tracing["on"] and now >= t_trace[0]:
            tracing["on"] = True
            tracing["log0"] = len(eng.batch_log)
            cap.start()
        elif cap is not None and tracing["on"] and not tracing["off"] \
                and now >= t_trace[1]:
            tracing["off"] = True
            tracing["log1"] = len(eng.batch_log)
            cap.stop()
        if reqs.due[i] > now:
            with span("bench.wait_due"):
                time.sleep(reqs.due[i] - now)
            continue
        with span("bench.submit"):
            while i < n and reqs.due[i] <= now:
                w, c = reqs.docs.doc(i)
                fut = eng.submit(w, c, key=reqs.keys[i])
                sent[i] = time.perf_counter() - t0
                futures[i] = fut
                fut.add_done_callback(functools.partial(stamp, i))
                i += 1
    rest = t0 + seconds - time.perf_counter()
    if rest > 0:
        time.sleep(rest)
    env.counter.armed = False
    log_end = len(eng.batch_log)
    if cap is not None and tracing["on"] and not tracing["off"]:
        tracing["off"] = True
        tracing["log1"] = len(eng.batch_log)
        cap.stop()
    wait([f for f in futures if f is not None], timeout=ANSWER_WAIT_S)
    t_wait = time.perf_counter() - t0
    lat = np.where(ok, done, t_wait) - reqs.due[:n]
    out = {"lat": lat, "done": done, "ok": ok, "sent": sent,
           "futures": futures,
           "batch_log": list(eng.batch_log[log_start:log_end])}
    if cap is not None and tracing["on"]:
        out["trace"] = cap.reduce()
        out["trace_launches"] = tracing["log1"] - tracing["log0"]
    return out
