"""Compile the main-path Pallas kernels for a described TPU v5e chip.

No chip is needed: the TPU compiler is installed and compiles for a
topology that is described, not attached.  It refuses what interpret mode
accepts — block shapes that break the (8, 128) tiling rule, value-level
dynamic slices inside a kernel, scalar stores to VMEM, scalar-prefetch
tables that overflow SMEM, packed rows loaded at unaligned offsets — so
these compiles guard the kernels at the reference cell (D=256, L=64,
K=128, W_s=8192, A=16) on every run of the suite.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and test workers import every file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.gs_sweep import gs_sweep_pallas
from repro.kernels.scheduled_sweep import scheduled_sweep_pallas
from repro.kernels.sharded_sweep import sharded_fold_pallas, sharded_probe_pallas
from repro.kernels.theta_sweep import quantize_phi, theta_sweep_pallas

D, L, K, W, A = 256, 64, 128, 8192, 16
SWEEP_KW = dict(alpha_m1=0.01, beta_m1=0.01, lane_align=128)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler for this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A described chip, with the persistent compilation cache off: an
    entry written for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile()


F32, I32, BOOL = jnp.float32, jnp.int32, jnp.bool_
STATS = [((D, L), I32), ((D, L), F32), ((D, L, K), F32), ((D, K), F32),
         ((W, K), F32), ((K,), F32)]
TABLE, ACTIVE, COLUMN, SCALAR = ((W, A), I32), ((D, L), BOOL), ((D, L), F32), ((), F32)


def _gs(emit_loglik):
    return (lambda *a: gs_sweep_pallas(*a[:6], wb=a[6], emit_loglik=emit_loglik,
                                       **SWEEP_KW),
            STATS + [SCALAR])


def _scheduled(emit_loglik):
    return (lambda *a: scheduled_sweep_pallas(*a[:8], wb=a[8],
                                              emit_loglik=emit_loglik,
                                              **SWEEP_KW),
            STATS + [TABLE, ACTIVE, SCALAR])


def _probe():
    return (lambda *a: sharded_probe_pallas(*a[:8], wb=a[8], **SWEEP_KW),
            STATS + [TABLE, ACTIVE, SCALAR])


def _fold():
    return (lambda *a: sharded_fold_pallas(*a[:10], wb=a[10],
                                           emit_loglik=True, **SWEEP_KW),
            STATS + [COLUMN, COLUMN, TABLE, ACTIVE, SCALAR])


def _theta(phi_dtype):
    def f(wid, est, ev, theta, phi, word_topics):
        values, scale = quantize_phi(phi, phi_dtype)
        return theta_sweep_pallas(wid, est, ev, theta, values, word_topics,
                                  scale, alpha_m1=0.01, num_sweeps=10,
                                  lane_align=128)
    return f, [((D, L), I32), COLUMN, COLUMN, ((D, K), F32), ((W, K), F32),
               TABLE]


LAUNCHES = {
    "gs_sweep": lambda: _gs(False),
    "gs_sweep-loglik": lambda: _gs(True),
    "scheduled_sweep": lambda: _scheduled(False),
    "scheduled_sweep-loglik": lambda: _scheduled(True),
    "theta_sweep-float32": lambda: _theta("float32"),
    "theta_sweep-bfloat16": lambda: _theta("bfloat16"),
    "theta_sweep-int8": lambda: _theta("int8"),
    "sharded_probe": _probe,
    "sharded_fold": _fold,
}


@pytest.mark.parametrize("launch", sorted(LAUNCHES))
def test_kernel_compiles_for_v5e(one_chip, launch):
    fn, shapes = LAUNCHES[launch]()
    compiled = _compile(fn, shapes, one_chip)
    assert "tpu_custom_call" in compiled.as_text()


def test_device_tier_gather_and_scatter_copy_no_table(one_chip):
    """The trainer's device row tier at PubMed's widths (W=141,043 rows,
    K=10^4 padded to the lanes, an 8,704-row step): its gather and its
    donated scatter need temporaries of about twice the step's rows (0.7
    GB), not a copy of the 5.7 GB table (6.1 GB, which an unaligned K
    costs), and the scatter is one operation, not a loop over the ids
    (which a scatter into part of each row costs)."""
    from repro.core.streaming import TIER_LANES, _tier_gather, _tier_scatter

    rows, k, n = 141_043, 10_000, 8_704
    lanes = -(-k // TIER_LANES) * TIER_LANES
    table = jax.ShapeDtypeStruct((rows, lanes), F32, sharding=one_chip)
    ids = jax.ShapeDtypeStruct((n,), I32, sharding=one_chip)
    new = jax.ShapeDtypeStruct((n, k), F32, sharding=one_chip)
    quarter = rows * lanes * 4 // 4
    gather = _tier_gather.lower(table, ids, k).compile().memory_analysis()
    assert gather.temp_size_in_bytes <= quarter
    scatter = _tier_scatter.lower(table, ids, new).compile()
    assert "while" not in scatter.as_text()      # one scatter, not a loop
    mem = scatter.memory_analysis()
    assert mem.temp_size_in_bytes <= quarter
    assert mem.alias_size_in_bytes >= rows * lanes * 4       # in place
