"""Pallas TPU kernel: fused frozen-φ inference (θ-only fixed point) — §2.4.

The paper's test-time protocol "infers the topic distribution from the
previously unseen documents incrementally with constant memory" (§2.4):
with the trained φ̂ FROZEN, fit θ̂ per held-out document by the limiting
fixed-point E-step (Cappé-style online EM's E-step with the M-step
switched off for φ)

    μ_{w,d}(k) ∝ θ_d(k) · φ_w(k)          (eq. 11, φ̂ frozen)
    θ̂_d(k)    = Σ_w x^{80%}_{w,d} μ_{w,d}(k)

and score the evaluation split with eq. 21,
P = exp(−Σ x^{20%} log Σ_k θ_d(k) φ_w(k) / Σ x^{20%}).

The legacy serving path (``perplexity.fit_theta_fixed_phi`` before this
kernel) materialised the dense (D, L, K) gathered φ rows, scanned a fixed
50 Jacobi sweeps, and then ran a second standalone (D, L, K) gather+einsum
pass for the eq. 21 evaluation.  Here the whole fixed point is ONE launch,
structured like ``gs_sweep_pallas``:

  * the grid is ``num_sweeps·L + L``: ``num_sweeps`` Jacobi sweeps over the
    token columns followed by L evaluation columns;
  * θ̂ (D, K) is carried in VMEM across all grid steps with
    ``input_output_aliases`` donation; a second VMEM accumulator collects
    the next sweep's fold so the Jacobi semantics (whole sweep against the
    sweep-start θ̂) are preserved;
  * φ (W_s, K) enters *already normalised* (eq. 10) and is never written —
    a constant-index VMEM block, fetched once for the whole launch;
  * the word ids are scalar-prefetched (``PrefetchScalarGridSpec``) and
    drive a per-document dynamic row gather — the (D, L, K) gathered-rows
    tensor is never materialised: live memory is O((W_s + D)·K), constant
    in the number of fixed-point sweeps (the §2.4 claim);
  * the trailing L evaluation columns re-walk the tokens against the FINAL
    θ̂ and emit per-token eq. 21 log-predictive partials for BOTH splits —
    ``x^{80%}·log lik`` (the convergence stop rule's eq. 3 measure) and
    ``x^{20%}·log lik`` (held-out perplexity) — so neither needs a
    standalone (D, L, K) pass;
  * the scheduled variant additionally scalar-prefetches per-word
    (W_s, A) active-topic ids — the §3.1 machinery reused at serving time
    with φ-mass-ranked active sets (see ``perplexity.serving_active_topics``)
    — and expands them in-kernel to a (D, K) lane mask restricting each
    token's topic support during the *fit*; the evaluation columns always
    use the full support, so eq. 21 stays exact.

Convergence is decided OUTSIDE the launch: the dispatch layer
(``ops.infer``) runs the kernel in ``check_every``-sweep chunks inside a
``lax.while_loop``, carrying θ̂ between launches and stopping when the
estimation-split perplexity moves less than ``rel_tol`` (the same relative
stop rule as training, ``LDAConfig.ppl_rel_tol``).

Quantized serving φ (``InferPlan.phi_dtype``): because φ is frozen and
read-only at serving time, it may enter the launch as bf16 or as int8
values with a per-row f32 scale (``quantize_phi``).  The kernel
dequantizes ON READ — each gathered (1, K) row is cast back to f32 (and
scaled, for int8) as it lands in the f32 ``rows`` scratch — so every
downstream fixed-point and eq. 21 operation is unchanged f32 arithmetic.
Only the big (W_s, K) φ block shrinks (2× for bf16, 4× for int8), which
is what doubles/quadruples the servable W_s×K per launch; the int8 scale
vector rides in SMEM next to the word ids.  The f32 path is bitwise
untouched: the quantized ref/cast code is not even staged when
``phi_norm`` arrives as f32.

VMEM budget: θ̂ in/out + the gathered-rows, accumulator and (scheduled)
mask scratches are (D, K) blocks next to the (W_s, K) φ block; the
dispatch falls back to the portable jnp mirror when the working set
exceeds the budget or the backend is not TPU.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analysis.budget import DEFAULT_VMEM_BUDGET
from repro.analysis.checks import kernel_fits_vmem
from repro.kernels.gs_sweep import (
    column_major,
    compiler_params,
    from_column_major,
)
from repro.kernels.scheduled_sweep import expand_lane_mask


#: Serving φ storage dtypes ``ops.infer`` accepts (InferPlan.phi_dtype).
PHI_DTYPES = ("float32", "bfloat16", "int8")

#: Minimum second-minor (sublane) tile extent per φ storage dtype — the
#: Mosaic layout constraint a compiled launch's W_s must be a multiple of.
PHI_SUBLANE = {"float32": 8, "bfloat16": 16, "int8": 32}

#: phi_dtype -> registered LaunchContract name (quantized variants).
_PHI_CONTRACT = {
    "float32": "theta_sweep",
    "bfloat16": "theta_sweep_bf16",
    "int8": "theta_sweep_int8",
}


def theta_fits_vmem(num_rows: int, num_docs: int, num_topics: int,
                    budget: int = DEFAULT_VMEM_BUDGET,
                    phi_dtype: str = "float32") -> bool:
    """Can the inference kernel's live VMEM set fit for one launch?

    Delegates to the ``theta_sweep`` contract in ``repro.analysis`` (or
    its quantized ``theta_sweep_bf16``/``theta_sweep_int8`` variant): the
    carried θ̂ pair (in + aliased out), the read-only φ block at the
    serving storage dtype, the rows/accumulator/mask scratches and the
    per-column split/loglik blocks, at the padded shapes.
    """
    return kernel_fits_vmem(_PHI_CONTRACT[phi_dtype], num_rows, num_docs,
                            num_topics, budget)


def quantize_phi(phi_norm: jax.Array, phi_dtype: str):
    """Quantize a normalised (W_s, K) φ block for read-only serving.

    Returns ``(values, scale)`` where ``scale`` is ``None`` except for
    int8, which uses symmetric per-row quantization: ``scale_w =
    max_k |φ_w(k)| / 127`` (1.0 for all-zero rows, e.g. vocab padding)
    and ``values = round(φ_w / scale_w)``.  Per-ROW scaling matters:
    dequantize-then-gather and gather-then-dequantize are then bitwise
    identical, so the in-kernel on-read dequantization matches the
    portable mirror exactly.
    """
    if phi_dtype == "float32":
        return phi_norm, None
    if phi_dtype == "bfloat16":
        return phi_norm.astype(jnp.bfloat16), None
    if phi_dtype == "int8":
        amax = jnp.max(jnp.abs(phi_norm), axis=-1)
        scale = jnp.where(amax > 0, amax / 127.0, 1.0).astype(jnp.float32)
        q = jnp.round(phi_norm / scale[:, None])
        return jnp.clip(q, -127, 127).astype(jnp.int8), scale
    raise ValueError(
        f"unknown phi_dtype {phi_dtype!r}; expected one of {PHI_DTYPES}"
    )


def dequantize_phi(values: jax.Array,
                   scale: Optional[jax.Array]) -> jax.Array:
    """Invert :func:`quantize_phi` (the portable mirror's read path)."""
    out = values.astype(jnp.float32)
    if scale is not None:
        out = out * scale[:, None]
    return out


def _make_theta_kernel(*, alpha_m1: float, k_actual: int, num_cols: int,
                       num_sweeps: int, active_topics: int,
                       quantized: bool = False, has_scale: bool = False,
                       row_tile: int = 1):
    """Kernel body for a static (sweeps, A, φ-dtype) configuration.

    Ref order: scalar prefetch (wid[, word-topics][, φ row scales]),
    inputs (est counts column, ev counts column, θ̂, φ), outputs (θ̂
    carried; est/ev log-predictive columns), scratch (gathered rows,
    sweep accumulator[, lane mask]).  ``active_topics == 0`` builds the
    dense variant; ``quantized`` casts each gathered φ row back to f32 on
    read (``has_scale`` additionally multiplies by the word's
    scalar-prefetched int8 scale) — the f32 variant stages no cast at all.

    A packed φ (bf16/int8) cannot be loaded one row at a dynamic offset:
    the compiler needs the offset aligned to the dtype's sublane tile.
    The quantized gather loads the ``row_tile``-row tile holding the word
    and keeps the word's row with a sublane select (exact: one value and
    zeros summed).
    """
    scheduled = active_topics > 0

    def kernel(*refs):
        rest = list(refs)
        wid_ref = rest.pop(0)
        wtop_ref = rest.pop(0) if scheduled else None
        scale_ref = rest.pop(0) if has_scale else None
        (cnt_ref, ev_ref, theta_in_ref, phi_ref,
         theta_ref, est_ref, evll_ref, rows_ref, acc_ref) = rest[:9]
        mask_ref = rest[9] if scheduled else None

        l = pl.program_id(0)
        D, K = theta_ref.shape
        col = jax.lax.rem(l, num_cols)

        @pl.when(l == 0)
        def _():
            theta_ref[...] = theta_in_ref[...]

        def theta_norm():
            # eq. 9 against the carried θ̂; padded lanes never reach the
            # likelihood (φ's padding lanes are zero), so no iota mask
            theta = theta_ref[...]
            den = theta.sum(-1, keepdims=True) + k_actual * alpha_m1
            return (theta + alpha_m1) / jnp.maximum(den, 1e-30)

        def gather(with_mask):
            # serial per-document row gather off the prefetched word ids;
            # the scheduled fit also expands the word's (A,) active-topic
            # ids into a lane mask (same idiom as scheduled_sweep)
            lane = jax.lax.broadcasted_iota(jnp.int32, (1, K), 1)

            def go(d, _):
                w = wid_ref[d, col]
                if quantized:
                    # dequantize on read: the f32 rows scratch receives
                    # exact f32 arithmetic from here on
                    base = pl.multiple_of((w // row_tile) * row_tile, row_tile)
                    tile = phi_ref[pl.ds(base, row_tile), :].astype(
                        rows_ref.dtype
                    )
                    sub = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 0)
                    row = jnp.where(sub == w - base, tile, 0.0).sum(
                        0, keepdims=True
                    )
                    if has_scale:
                        row = row * scale_ref[w]
                else:
                    row = phi_ref[pl.ds(w, 1), :]
                rows_ref[pl.ds(d, 1), :] = row
                if with_mask:
                    mask_ref[pl.ds(d, 1), :] = expand_lane_mask(
                        wtop_ref, w, active_topics, lane, theta_in_ref.dtype
                    )
                return 0
            jax.lax.fori_loop(0, D, go, 0)

        def sweep_col():
            cnt = cnt_ref[0]                    # (D, 1)
            th_n = theta_norm()
            gather(scheduled)
            num = th_n * rows_ref[...]
            if scheduled:
                num = num * mask_ref[...]       # fit support: active set only
            denom = jnp.maximum(num.sum(-1, keepdims=True), 1e-30)
            contrib = cnt * (num / denom)       # x^{80%}·μ for this column

            @pl.when(col == 0)
            def _():
                acc_ref[...] = contrib

            @pl.when(col != 0)
            def _():
                acc_ref[...] = acc_ref[...] + contrib

            # last column: the fold becomes the next sweep's θ̂ (Jacobi —
            # the whole sweep ran against the sweep-start statistics)
            @pl.when(col == num_cols - 1)
            def _():
                theta_ref[...] = acc_ref[...]

        def eval_col():
            # eq. 21 phase against the FINAL θ̂, full topic support (the
            # scheduled variant restricts only the fit, never the score)
            gather(False)
            lik = (theta_norm() * rows_ref[...]).sum(-1, keepdims=True)
            ll = jnp.log(jnp.maximum(lik, 1e-30))
            est_ref[0] = cnt_ref[0] * ll        # eq. 3 stop-rule partial
            evll_ref[0] = ev_ref[0] * ll        # eq. 21 partial

        @pl.when(l < num_sweeps * num_cols)
        def _():
            sweep_col()

        @pl.when(l >= num_sweeps * num_cols)
        def _():
            eval_col()

    return kernel


@functools.partial(
    jax.jit,
    static_argnames=("alpha_m1", "num_sweeps", "lane_align", "interpret"),
)
def theta_sweep_pallas(
    word_ids: jax.Array,       # (D, L) int32 — rows into phi_norm
    est_counts: jax.Array,     # (D, L) float32 — estimation (80%) split
    ev_counts: jax.Array,      # (D, L) float32 — evaluation (20%) split
    theta: jax.Array,          # (D, K) θ̂ sufficient statistics (carried)
    phi_norm: jax.Array,       # (W_s, K) NORMALISED φ (eq. 10), frozen;
                               # f32, bf16 or int8 (see quantize_phi)
    word_topics: Optional[jax.Array] = None,  # (W_s, A) int32: scheduled fit
    phi_scale: Optional[jax.Array] = None,    # (W_s,) f32: int8 row scales
    *,
    alpha_m1: float,
    num_sweeps: int,
    lane_align: int = 1,       # pad K to this multiple (128 for compiled TPU)
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``num_sweeps`` frozen-φ fixed-point sweeps + the eq. 21 phase, fused.

    Returns ``(theta (D, K), est_ll (D, L), ev_ll (D, L))`` — the updated
    θ̂ statistics and the per-token log-predictive partials
    ``x·log Σ_k θ_d(k) φ_w(k)`` of the estimation and evaluation splits,
    both measured against the final θ̂ inside the launch.

    Documents pad to the 8-sublane boundary with zero-count slots (zero
    counts ⇒ zero θ̂ fold and zero partials, so padding is exact);
    ``lane_align`` pads the topic axis — φ's padded lanes carry zeros, so
    they never enter the responsibilities or the likelihood.

    A non-f32 ``phi_norm`` selects the quantized-read variant: the φ
    block stays at its storage dtype in VMEM and each gathered row is
    dequantized on read (int8 additionally needs ``phi_scale``, the
    per-row scales of :func:`quantize_phi`, scalar-prefetched to SMEM).
    """
    if num_sweeps < 1:
        raise ValueError("num_sweeps must be >= 1")
    D, L = word_ids.shape
    K = theta.shape[-1]
    Wrows = phi_norm.shape[0]
    scheduled = word_topics is not None
    A = word_topics.shape[-1] if scheduled else 0
    quantized = phi_norm.dtype != theta.dtype
    has_scale = phi_scale is not None
    if phi_norm.dtype == jnp.int8 and not has_scale:
        raise ValueError("int8 phi_norm requires phi_scale row scales")

    pad_d = (-D) % 8
    pad_k = (-K) % lane_align if lane_align > 1 else 0
    Dp, Kp = D + pad_d, K + pad_k
    if pad_d or pad_k:
        word_ids = jnp.pad(word_ids, ((0, pad_d), (0, 0)))
        est_counts = jnp.pad(est_counts, ((0, pad_d), (0, 0)))
        ev_counts = jnp.pad(ev_counts, ((0, pad_d), (0, 0)))
        theta = jnp.pad(theta, ((0, pad_d), (0, pad_k)))
        phi_norm = jnp.pad(phi_norm, ((0, 0), (0, pad_k)))
    # a quantized gather reads whole sublane tiles: pad W_s to the tile
    row_tile = PHI_SUBLANE[jnp.dtype(phi_norm.dtype).name] if quantized else 1
    pad_w = (-Wrows) % row_tile
    if pad_w:
        phi_norm = jnp.pad(phi_norm, ((0, pad_w), (0, 0)))
        Wrows += pad_w
        if has_scale:
            phi_scale = jnp.pad(phi_scale, ((0, pad_w),), constant_values=1.0)

    kernel = _make_theta_kernel(
        alpha_m1=alpha_m1, k_actual=K, num_cols=L, num_sweeps=num_sweeps,
        active_topics=A, quantized=quantized, has_scale=has_scale,
        row_tile=row_tile,
    )
    grid_len = num_sweeps * L + L              # sweeps + eq. 21 columns

    def idx(fn):
        # trailing args are the scalar-prefetch refs (wid[, wtop][, scale])
        return lambda l, *scalars: fn(l)

    col_of = lambda l: jax.lax.rem(l, L)
    # the eq. 21 outputs hold block 0 through the sweeps, which never write
    # it, then walk the columns once: no output block is revisited
    eval_of = lambda l: jnp.maximum(l - num_sweeps * L, 0)

    in_specs = [
        pl.BlockSpec((1, Dp, 1), idx(lambda l: (col_of(l), 0, 0))),
        pl.BlockSpec((1, Dp, 1), idx(lambda l: (col_of(l), 0, 0))),
        pl.BlockSpec((Dp, Kp), idx(lambda l: (0, 0))),
        pl.BlockSpec((Wrows, Kp), idx(lambda l: (0, 0))),
    ]
    out_specs = [
        pl.BlockSpec((Dp, Kp), idx(lambda l: (0, 0))),
        pl.BlockSpec((1, Dp, 1), idx(lambda l: (eval_of(l), 0, 0))),
        pl.BlockSpec((1, Dp, 1), idx(lambda l: (eval_of(l), 0, 0))),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((Dp, Kp), theta.dtype),
        jax.ShapeDtypeStruct((L, Dp, 1), theta.dtype),
        jax.ShapeDtypeStruct((L, Dp, 1), theta.dtype),
    ]
    scratch_shapes = [
        pltpu.VMEM((Dp, Kp), theta.dtype),     # gathered φ rows
        pltpu.VMEM((Dp, Kp), theta.dtype),     # sweep-fold accumulator
    ]
    if scheduled:
        scratch_shapes.append(pltpu.VMEM((Dp, Kp), theta.dtype))  # lane mask

    operands = [word_ids]
    if scheduled:
        operands.append(word_topics.reshape(-1))
    if has_scale:
        operands.append(phi_scale)
    n_scalars = len(operands)
    operands += [column_major(est_counts), column_major(ev_counts), theta,
                 phi_norm]
    # flat operands: wid(0) [wtop] [scale] est ev theta phi — θ̂ donated
    theta_idx = n_scalars + 2

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_scalars,
        grid=(grid_len,),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch_shapes,
    )
    theta_out, est_out, ev_out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases={theta_idx: 0},
        compiler_params=compiler_params(),
        interpret=interpret,
    )(*operands)

    est_ll = from_column_major(est_out)[:D]    # (D, L) per-token partials
    ev_ll = from_column_major(ev_out)[:D]
    return theta_out[:D, :K], est_ll, ev_ll
