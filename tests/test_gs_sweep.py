"""Fused Gauss-Seidel sweep: interpret-mode kernel parity, residual emission,
and fused-vs-legacy dispatch equivalence.

The contract: ``kernels.ops.gs_sweep`` (one launch / delta-compacted scan)
computes exactly the column-serial blocked-IEM sweep that ``lax.scan`` +
full-matrix segment-sum used to, and its emitted residual equals the
post-hoc ``scheduling.full_sweep_residuals`` measurement.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import em, foem
from repro.core import scheduling as sched_lib
from repro.core.types import LDAConfig, LocalState, MinibatchData
from repro.kernels import ops as kops
from repro.kernels.gs_sweep import gs_sweep_pallas


def _state(D, L, K, W, seed=0, unique_cols=False, zero_counts=False):
    rng = np.random.default_rng(seed)
    if unique_cols:
        # distinct words within every column -> scatter order can't matter,
        # so the serial kernel and the XLA scatter-add agree bitwise
        wid = np.stack(
            [rng.permutation(D) + l * D for l in range(L)], axis=1
        ).astype(np.int32)
        assert W >= D * L
    else:
        wid = rng.integers(0, W, (D, L)).astype(np.int32)
    lo = 0 if zero_counts else 1
    cnt = rng.integers(lo, 5, (D, L)).astype(np.float32)
    mu = rng.dirichlet(np.ones(K), (D, L)).astype(np.float32)
    batch = MinibatchData(jnp.asarray(wid), jnp.asarray(cnt))
    mu = jnp.asarray(mu)
    theta = em.fold_theta(mu, batch.counts)
    phi, ptot = em.fold_phi(mu, batch.counts, batch.word_ids, W)
    return batch, LocalState(mu=mu, theta_dk=theta), phi, ptot


def _sweep_args(cfg, W):
    return dict(alpha_m1=cfg.alpha_m1, beta_m1=cfg.beta_m1,
                wb=W * cfg.beta_m1)


# ---------------------------------------------------------------------------
# Pallas kernel (interpret mode) vs the paper's exact serial IEM
# ---------------------------------------------------------------------------

def test_gs_sweep_pallas_matches_serial_oracle():
    """Fused kernel ≡ paper Fig. 2 serial IEM (disjoint words per doc),
    values/θ̂/φ̂ to ≤ 1e-5 relative error over multiple sweeps."""
    rng = np.random.default_rng(0)
    L, K, W, sweeps = 8, 5, 40, 4
    word_ids = rng.permutation(W)[:L].reshape(1, L).astype(np.int32)
    counts = rng.integers(1, 5, size=(1, L)).astype(np.float32)
    mu0 = rng.dirichlet(np.ones(K), size=(1, L)).astype(np.float32)
    cfg = LDAConfig(num_topics=K, vocab_size=W)
    mu_np, theta_np, phi_np = em.iem_exact_numpy(
        word_ids, counts, mu0, cfg, sweeps=sweeps
    )

    batch = MinibatchData(jnp.asarray(word_ids), jnp.asarray(counts))
    mu = jnp.asarray(mu0)
    theta = em.fold_theta(mu, batch.counts)
    phi, ptot = em.fold_phi(mu, batch.counts, batch.word_ids, W)
    for _ in range(sweeps):
        mu, _, theta, phi, ptot = kops.gs_sweep(
            batch.word_ids, batch.counts, mu, theta, phi, ptot,
            **_sweep_args(cfg, W), interpret=True,
        )
    scale = np.abs(mu_np).max()
    np.testing.assert_allclose(np.asarray(mu), mu_np,
                               atol=1e-5 * max(scale, 1.0))
    np.testing.assert_allclose(np.asarray(theta), theta_np,
                               rtol=2e-5, atol=1e-5 * np.abs(theta_np).max())
    np.testing.assert_allclose(np.asarray(phi), phi_np,
                               rtol=2e-5, atol=1e-5 * np.abs(phi_np).max())


@pytest.mark.parametrize("D,L,K,W", [(5, 6, 7, 64), (8, 4, 16, 64),
                                     (12, 9, 5, 128)])
def test_gs_sweep_pallas_matches_portable(D, L, K, W):
    """Interpret-mode kernel ≡ portable delta-compacted path on CPU —
    including the padded-document path (D not a multiple of 8).  Tolerance
    is a couple of float32 ulps: the two paths build different XLA graphs,
    so fusion/FMA choices may differ in the last bit."""
    cfg = LDAConfig(num_topics=K, vocab_size=W)
    batch, local, phi, ptot = _state(D, L, K, W, seed=D, unique_cols=True)
    a = kops.gs_sweep(batch.word_ids, batch.counts, local.mu, local.theta_dk,
                      phi, ptot, **_sweep_args(cfg, W), use_pallas=False)
    b = kops.gs_sweep(batch.word_ids, batch.counts, local.mu, local.theta_dk,
                      phi, ptot, **_sweep_args(cfg, W), interpret=True)
    for name, x, y in zip(("mu", "res", "theta", "phi", "ptot"), a, b):
        np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), rtol=2e-6, atol=1e-5,
            err_msg=name,
        )


def test_gs_sweep_padding_bitwise_invisible():
    """The wrapper's document padding must be bitwise-invisible: feeding a
    pre-padded minibatch (zero-count slots) through the same kernel and
    slicing gives identical bits to the auto-padded call."""
    D, L, K, W = 12, 6, 5, 96
    cfg = LDAConfig(num_topics=K, vocab_size=W)
    batch, local, phi, ptot = _state(D, L, K, W, seed=4, unique_cols=True)
    auto = kops.gs_sweep(
        batch.word_ids, batch.counts, local.mu, local.theta_dk, phi, ptot,
        **_sweep_args(cfg, W), interpret=True,
    )
    Dp = 16
    pad = ((0, Dp - D), (0, 0))
    manual = kops.gs_sweep(
        jnp.pad(batch.word_ids, pad), jnp.pad(batch.counts, pad),
        jnp.pad(local.mu, pad + ((0, 0),)), jnp.pad(local.theta_dk, pad),
        phi, ptot, **_sweep_args(cfg, W), interpret=True,
    )
    for name, x, y in zip(("mu", "res", "theta", "phi", "ptot"), auto, manual):
        y = np.asarray(y)
        if y.ndim >= 1 and y.shape[0] == Dp and name in ("mu", "res",
                                                         "theta"):
            y = y[:D]
        np.testing.assert_array_equal(np.asarray(x), y, err_msg=name)


def test_gs_sweep_lane_padding_masked():
    """K padded to the lane boundary (compiled-TPU layout) must not leak
    renormalisation mass into the padding lanes."""
    D, L, K, W = 8, 6, 7, 80
    cfg = LDAConfig(num_topics=K, vocab_size=W)
    batch, local, phi, ptot = _state(D, L, K, W, seed=3)
    ref = kops.gs_sweep(batch.word_ids, batch.counts, local.mu,
                        local.theta_dk, phi, ptot, **_sweep_args(cfg, W),
                        use_pallas=False)
    padded = gs_sweep_pallas(batch.word_ids, batch.counts, local.mu,
                             local.theta_dk, phi, ptot,
                             **_sweep_args(cfg, W), lane_align=8,
                             interpret=True)
    for name, x, y in zip(("mu", "res", "theta", "phi", "ptot"), ref, padded):
        np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), atol=1e-6, err_msg=name
        )


def test_gs_sweep_zero_count_slots_inert():
    """Padding slots (count 0) must not move any statistic."""
    D, L, K, W = 8, 5, 4, 32
    cfg = LDAConfig(num_topics=K, vocab_size=W)
    batch, local, phi, ptot = _state(D, L, K, W, seed=7, zero_counts=True)
    mu, res, theta, phi_o, ptot_o = kops.gs_sweep(
        batch.word_ids, batch.counts, local.mu, local.theta_dk, phi, ptot,
        **_sweep_args(cfg, W), interpret=True,
    )
    zero = np.asarray(batch.counts) == 0
    assert np.all(np.asarray(res)[zero] == 0.0)
    np.testing.assert_allclose(          # mass conservation incl. zeros
        np.asarray(ptot_o.sum()), float(batch.counts.sum()), rtol=1e-4
    )
    np.testing.assert_allclose(
        np.asarray(phi_o.sum(0)), np.asarray(ptot_o), rtol=1e-4
    )


# ---------------------------------------------------------------------------
# Residual emission ≡ post-hoc full_sweep_residuals
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("interpret", [False, True])
def test_gs_sweep_residual_equivalence(interpret):
    D, L, K, W = 8, 6, 5, 48
    cfg = LDAConfig(num_topics=K, vocab_size=W)
    batch, local, phi, ptot = _state(D, L, K, W, seed=11)
    # interpret=True exercises the kernel body; False the portable oracle
    how = dict(interpret=True) if interpret else dict(use_pallas=False)
    mu_new, res, theta, phi_o, ptot_o = kops.gs_sweep(
        batch.word_ids, batch.counts, local.mu, local.theta_dk, phi, ptot,
        **_sweep_args(cfg, W), **how,
    )
    emitted = sched_lib.residuals_from_sweep(res, batch.word_ids, W)
    measured = sched_lib.full_sweep_residuals(
        mu_new, local.mu, batch.counts, batch.word_ids, W
    )
    np.testing.assert_allclose(np.asarray(emitted.r_wk),
                               np.asarray(measured.r_wk), atol=1e-6)
    np.testing.assert_allclose(np.asarray(emitted.r_w),
                               np.asarray(measured.r_w), atol=1e-5)


# ---------------------------------------------------------------------------
# Dispatch: blocked_iem_sweep fused vs legacy scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("D,L,K,W", [(6, 8, 5, 64), (16, 12, 8, 200)])
def test_blocked_iem_sweep_fused_matches_scan(D, L, K, W):
    cfg = LDAConfig(num_topics=K, vocab_size=W)
    batch, local, phi, ptot = _state(D, L, K, W, seed=D + L)
    l_scan, dwk_s, dk_s = em.blocked_iem_sweep(
        batch, local, phi, ptot,
        dataclasses.replace(cfg, sweep_impl="scan"),
    )
    l_fused, dwk_f, dk_f = em.blocked_iem_sweep(batch, local, phi, ptot, cfg)
    np.testing.assert_allclose(np.asarray(l_scan.mu), np.asarray(l_fused.mu),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(l_scan.theta_dk),
                               np.asarray(l_fused.theta_dk), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(dwk_s), np.asarray(dwk_f),
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(dk_s), np.asarray(dk_f), rtol=1e-4,
                               atol=2e-4)


def test_coarse_blocks_keep_legacy_path():
    """B < L can't be expressed column-serially; the dispatch must keep the
    blocked scan (and still satisfy the delta contract)."""
    D, L, K, W = 6, 8, 5, 64
    cfg = LDAConfig(num_topics=K, vocab_size=W, iem_blocks=4)
    batch, local, phi, ptot = _state(D, L, K, W, seed=2)
    loc, dwk, dk = em.blocked_iem_sweep(batch, local, phi, ptot, cfg)
    np.testing.assert_allclose(
        np.asarray(dwk.sum(0)), np.asarray(dk), rtol=1e-4, atol=1e-4
    )
    np.testing.assert_allclose(
        np.asarray(loc.theta_dk.sum(-1)),
        np.asarray(batch.counts.sum(1)), rtol=1e-4
    )


def test_foem_minibatch_fused_matches_scan():
    """The whole inner loop (warm-up + residual init + scheduled sweeps)
    agrees between the fused and legacy sweep implementations."""
    D, L, K, W = 8, 10, 6, 80
    cfg = LDAConfig(num_topics=K, vocab_size=W, max_sweeps=6,
                    active_topics=3, ppl_check_every=2)
    batch, local, phi, ptot = _state(D, L, K, W, seed=5)
    key = jax.random.PRNGKey(0)
    zeros_wk = jnp.zeros((W, K), jnp.float32)
    zeros_k = jnp.zeros((K,), jnp.float32)
    r_fused = foem.foem_minibatch(key, batch, zeros_wk, zeros_k, cfg)
    r_scan = foem.foem_minibatch(
        key, batch, zeros_wk, zeros_k,
        dataclasses.replace(cfg, sweep_impl="scan"),
    )
    assert int(r_fused.diag.sweeps_run) == int(r_scan.diag.sweeps_run)
    np.testing.assert_allclose(np.asarray(r_fused.phi_wk),
                               np.asarray(r_scan.phi_wk), atol=2e-4)
    np.testing.assert_allclose(np.asarray(r_fused.scheduler.r_wk),
                               np.asarray(r_scan.scheduler.r_wk), atol=2e-4)
    np.testing.assert_allclose(float(r_fused.diag.final_train_ppl),
                               float(r_scan.diag.final_train_ppl), rtol=1e-4)


def test_traced_vocab_size_reaches_kernels():
    """The streaming trainer passes the live vocab size as a *traced* jit
    argument, so wb = W·(β−1) reaches the kernel wrappers as a tracer —
    they must take it as an operand, not a jit-static (regression: a
    static wb raised 'Non-hashable static arguments' at trace time)."""
    D, L, K, W = 8, 5, 4, 32
    cfg = LDAConfig(num_topics=K, vocab_size=W)
    batch, local, phi, ptot = _state(D, L, K, W, seed=1)

    @jax.jit
    def run(live_w):
        return em.gs_sweep_with_residuals(
            batch, local, phi, ptot, cfg, vocab_size=live_w, interpret=True
        ).phi_wk

    traced = run(jnp.int32(W))
    eager = em.gs_sweep_with_residuals(
        batch, local, phi, ptot, cfg, interpret=True
    ).phi_wk
    np.testing.assert_allclose(np.asarray(traced), np.asarray(eager),
                               atol=1e-6)


# ---------------------------------------------------------------------------
# Dispatch record: which path a trace took, and why
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("case, path, reason", [
    ("auto", "portable", "no TPU"),
    ("forced_portable", "portable", "forced"),
    ("forced_interpret", "interpret", "forced"),
    ("tpu_fits", "pallas", "auto"),
    ("tpu_vmem", "portable", "VMEM"),
    ("tpu_ragged", "portable", "sublane"),
])
def test_sweep_dispatch_log_records_path_and_reason(monkeypatch, case, path,
                                                    reason):
    """``ops.sweep`` records each trace-time choice between a kernel and
    the portable path with its reason (traced only: ``eval_shape``)."""
    W = 13 if case == "tpu_ragged" else 16
    batch, local, phi, ptot = _state(4, 3, 8, W)
    kw = dict(alpha_m1=0.01, beta_m1=0.01, wb=W * 0.01)
    if case == "forced_portable":
        kw["use_pallas"] = False
    elif case == "forced_interpret":
        kw["interpret"] = True
    if case.startswith("tpu"):
        monkeypatch.setattr(kops, "on_tpu", lambda: True)
        monkeypatch.setattr(kops, "fits_vmem",
                            lambda *a: case != "tpu_vmem")
    mark = max([d.seq for d in kops.dispatch_log()], default=-1)
    jax.eval_shape(
        lambda *a: kops.sweep(*a, **kw),
        batch.word_ids, batch.counts, local.mu, local.theta_dk, phi, ptot,
    )
    (got,) = kops.dispatch_log(since=mark)
    assert (got.entry, got.path, got.reason) == ("sweep", path, reason)
    assert got.shape == (4, 3, 8, W)
    assert str(got) == f"sweep {path}: {reason}"


@pytest.mark.parametrize("vmem_fits", [True, False])
def test_infer_dispatch_log_records_path_and_reason(monkeypatch, vmem_fits):
    """``ops.infer`` records its kernel-or-portable choice the same way."""
    rng = np.random.default_rng(0)
    D, L, K, W = 4, 3, 8, 16
    wid = jnp.asarray(rng.integers(0, W, (D, L)), jnp.int32)
    cnt = jnp.ones((D, L), jnp.float32)
    phi = jnp.asarray(rng.dirichlet(np.ones(W), K).T, jnp.float32)
    monkeypatch.setattr(kops, "on_tpu", lambda: True)
    monkeypatch.setattr(kops, "theta_fits_vmem", lambda *a, **k: vmem_fits)
    mark = max([d.seq for d in kops.dispatch_log()], default=-1)
    jax.eval_shape(
        lambda w, c, t, p: kops.infer(w, c, t, p, alpha_m1=0.01,
                                      max_sweeps=10),
        wid, cnt, jnp.zeros((D, K), jnp.float32), phi,
    )
    (got,) = kops.dispatch_log(since=mark)
    want = ("pallas", "auto") if vmem_fits else ("portable", "VMEM")
    assert (got.entry, got.path, got.reason) == ("infer",) + want
