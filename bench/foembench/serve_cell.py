"""A serving cell: an open loop of requests into the program's normal
serving front, against a frozen φ̂ made from the generator's topics.  With
the mix's ``replicas`` 1 (the default) that is ``ServingEngine.submit``
(``AdmissionRouter`` → one launcher → ``TopicServer`` row gather →
``ops.infer``); with N > 1 it is a thread ``ReplicaPool`` of N
``TopicServer``s over the one store, each pinned to its own chip, behind the
same router settings (``ReplicaPool``'s defaults for the rest).

Set-up writes φ̂ into a ``ParameterStore``, builds the front and prewarms
its (L, W_s) trace grid (on every replica).  The window sends every request
at its due time; each request's latency runs from its due time to the
done-callback of its future, so a late generator or a queue shows as
latency.  A request that fails, or has not answered a minute after the
window closed, is a miss.
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

from foembench import checks, device, traffic
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
    replicas = int(mix.get("replicas", 1))
    servers = [TopicServer(store, lda, fit_sweeps=int(s["fit_sweeps"]),
                           check_every=int(s["check_every"]),
                           rel_tol=float(s["rel_tol"]),
                           vocab_pad=int(s["vocab_pad"]),
                           phi_dtype="bfloat16" if control
                           else s["phi_dtype"])
               for _ in range(replicas)]
    if fault is not None:
        for server in servers:
            fault(server)
    router = dict(max_batch=int(s["max_batch"]),
                  bucket_multiple=int(s["bucket_multiple"]),
                  max_len=int(s["max_len"]),
                  max_delay_ms=float(s["max_delay_ms"]),
                  seed=seed % (1 << 31))
    if replicas == 1:
        front = ServingEngine(servers[0], **router)
    else:
        from repro.launch.replica import ReplicaPool

        front = ReplicaPool(backend="thread", servers=servers,
                            replicas=replicas, **router)
    out = {"devices": env.devices, "notes": []}
    try:
        front.prewarm()
        compiled = ServingEngine.compile_count()
        reqs = traffic.open_loop(cfg, mix, seed, seconds, rate)
        n = int(np.searchsorted(reqs.due, seconds))
        log = kops.dispatch_log()
        mark = log[-1].seq if log else -1
        setup_s = time.perf_counter() - t_setup
        res = _window(front, front.router.batch_log, reqs, n, seconds, env,
                      trace, float(mix["trace_seconds"]))
        env.counter.armed = False
        new_dispatch = kops.dispatch_log(since=mark)
        infers = [d for d in kops.dispatch_log() if d.entry == "infer"]
        out["memory_peak_bytes"] = env.memory_peak()
        if replicas > 1:
            pool = front.metrics()
            per_chip = [device.memory_peak_bytes([d]) for d in env.devices]
            out["notes"] += [
                f"{replicas} thread ReplicaPool replicas behind one "
                f"AdmissionRouter (ReplicaPool defaults: max_inflight "
                f"{front.balancer.cap}, queue_depth {front.router.queue_depth})"
                f"; batches dispatched per replica {pool['dispatch']}, deaths "
                f"{pool['deaths']}, respawns {pool['respawns']}",
                f"peak bytes per chip {per_chip}: "
                f"{sum(1 for b in per_chip if b > 0)} of {len(per_chip)} "
                "chips used",
            ]
    finally:
        env.counter.armed = False
        front.close()
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
        f"{ServingEngine.compile_count()}",
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
    if replicas > 1:
        # the window's launches by the replica that served each
        out["ctx"]["dispatch"] = {
            rid: sum(1 for b in log_w if b.get("replica") == rid)
            for rid in range(replicas)}
    if res.get("trace") is not None:
        red = res["trace"]
        out["ctx"]["trace"] = red
        out["ctx"]["trace_launches"] = res["trace_launches"]
        out["notes"].append(
            f"trace: {res['trace_launches']} launches, {red.window_s:.3f} s, "
            f"{len(red.device_ops)} device operations on "
            f"{len({e.plane for e in red.device_ops})} device planes")

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
    ref = cell.reference
    theta_ref = ref.infer_theta(
        [reqs.docs.doc(i) for i in pick], reqs.keys[pick],
        ref.normalized_phi(phi, phi_k, lda.W, lda.beta_m1),
        alpha_m1=lda.alpha_m1, sweeps=int(s["fit_sweeps"]),
        bucket=int(s["bucket_multiple"]))
    out["checks"] = checks.serving(theta, theta_ref, cfg["limits"]["serve"])
    out["notes"].append(
        f"reference: {len(pick)} requests (longest {int(sizes.max()) if len(sizes) else 0}"
        f" words) in {time.perf_counter() - t_ref:.1f} s")
    shutil.rmtree(work_dir, ignore_errors=True)
    return out


def _window(front, batch_log, reqs, n: int, seconds: float, env,
            trace: bool, trace_seconds: float) -> Dict:
    """Send requests [0, n) at their due times through ``front.submit``;
    return latencies etc. and the window's records of ``batch_log``."""
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
    log_start = len(batch_log)

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
            tracing["log0"] = len(batch_log)
            cap.start()
        elif cap is not None and tracing["on"] and not tracing["off"] \
                and now >= t_trace[1]:
            tracing["off"] = True
            tracing["log1"] = len(batch_log)
            cap.stop()
        if reqs.due[i] > now:
            with span("bench.wait_due"):
                time.sleep(reqs.due[i] - now)
            continue
        with span("bench.submit"):
            while i < n and reqs.due[i] <= now:
                w, c = reqs.docs.doc(i)
                fut = front.submit(w, c, key=reqs.keys[i])
                sent[i] = time.perf_counter() - t0
                futures[i] = fut
                fut.add_done_callback(functools.partial(stamp, i))
                i += 1
    rest = t0 + seconds - time.perf_counter()
    if rest > 0:
        time.sleep(rest)
    env.counter.armed = False
    log_end = len(batch_log)
    if cap is not None and tracing["on"] and not tracing["off"]:
        tracing["off"] = True
        tracing["log1"] = len(batch_log)
        cap.stop()
    wait([f for f in futures if f is not None], timeout=ANSWER_WAIT_S)
    t_wait = time.perf_counter() - t0
    lat = np.where(ok, done, t_wait) - reqs.due[:n]
    out = {"lat": lat, "done": done, "ok": ok, "sent": sent,
           "futures": futures,
           "batch_log": list(batch_log[log_start:log_end])}
    if cap is not None and tracing["on"]:
        out["trace"] = cap.reduce()
        out["trace_launches"] = tracing["log1"] - tracing["log0"]
    return out
