"""The Pallas kernels under the TPU compiler, for a described v5e chip.

Nothing here runs a kernel: each test lowers and compiles one kernel at its
full-width shape for one chip of a described ``v5e:2x2`` topology, which is
what the TPU compiler would accept or refuse on the chip itself. Interpret
mode (every other kernel test) cannot see tiling, gather or VMEM limits.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library at a time, and a worker that
decided at import whether these tests exist would desynchronise the
parallel test run's collection.
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core.engine import ConfigError, EngineConfig
from repro.kernels import cycle, lif_update, ops, spike_deliver

# Full-width shapes: the MAM benchmark at its published in-degrees
# (K = 3000 per pathway) cut to 8 areas x 4096 neurons -- one chip's share.
N_AREAS, N_PER_AREA, K = 8, 4096, 3000
N = N_AREAS * N_PER_AREA
D, STEPS_LO, R_SPAN = 10, 1, 25

LIF_KW = dict(p11=0.9, p21=0.1, p22=0.99, v_th=15.0, v_reset=0.0,
              t_ref_steps=20, interpret=False)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A TPU executable written to the persistent cache cannot be read back
    # without a chip; keep these compiles out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile()


def test_lif_update_compiles_for_v5e(one_chip):
    rows = N // lif_update.LANES
    state = ((rows, lif_update.LANES), jnp.float32)
    ints = ((rows, lif_update.LANES), jnp.int32)
    compiled = _compile(
        functools.partial(lif_update.lif_update_pallas, **LIF_KW),
        one_chip, state, state, ints, state, ints)
    assert "tpu_custom_call" in compiled.as_text()


def test_spike_deliver_compiles_for_v5e(one_chip):
    tile = (spike_deliver.TILE_N, K)
    compiled = _compile(
        functools.partial(spike_deliver.spike_deliver_pallas,
                          steps_lo=STEPS_LO, r_span=R_SPAN, interpret=False),
        one_chip, (tile, jnp.float32), (tile, jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()


def _superstep_lif(one_chip):
    w_width = D + STEPS_LO + R_SPAN - 1
    row_f, row_i = ((N_AREAS, N_PER_AREA), jnp.float32), \
        ((N_AREAS, N_PER_AREA), jnp.int32)
    syn = (N_AREAS, N_PER_AREA, K)
    kw = dict(LIF_KW, d_win=D, steps_lo=STEPS_LO, r_span=R_SPAN, seed=42,
              w_ext=88.0)
    return _compile(
        functools.partial(cycle.superstep_lif_pallas, **kw), one_chip,
        row_f, row_f, row_i, ((N_AREAS, N_PER_AREA, w_width), jnp.float32),
        row_f, row_i, row_i, (syn, jnp.int32), (syn, jnp.float32),
        (syn, jnp.int32), ((1,), jnp.int32))


def _superstep_iaf(one_chip):
    w_width = D + STEPS_LO + R_SPAN - 1
    row_i = ((N_AREAS, N_PER_AREA), jnp.int32)
    syn = (N_AREAS, N_PER_AREA, K)
    return _compile(
        functools.partial(cycle.superstep_iaf_pallas, d_win=D,
                          steps_lo=STEPS_LO, r_span=R_SPAN, interpret=False),
        one_chip, row_i, ((N_AREAS, N_PER_AREA, w_width), jnp.float32),
        row_i, row_i, (syn, jnp.int32), (syn, jnp.float32), (syn, jnp.int32))


# The benchmark cell's event deposits (mam_bench.ground: 8 x 3,072 neurons,
# outgoing tables widened to 3,337, static packet bounds 1,030 per area and
# 4,145 per cycle, int8 delays): the intra deposit runs each area's packet
# into its 40-slot ring, the window-end inter deposit takes the window's 10
# packets into the 110-slot ring.
CELL_N, CELL_K_OUT = 3072, 3337


@pytest.mark.parametrize("fn,ring,ids,table", [
    (ops.event_deliver_per_area, (N_AREAS, CELL_N, 40), (N_AREAS, 1030),
     (N_AREAS, CELL_N, CELL_K_OUT)),
    (ops.event_deliver_block, (N_AREAS * CELL_N, 110), (D, 4145),
     (N_AREAS * CELL_N, CELL_K_OUT)),
], ids=["intra", "inter"])
def test_chunked_event_deposit_compiles_for_v5e(one_chip, fn, ring, ids,
                                                table):
    """The event deposit's chunk loop (a data-dependent ``while`` over
    ``dynamic_slice``d packet columns) at the cell's shapes."""
    assert ops.deposit_chunk(CELL_K_OUT, ids[-1]) < ids[-1]
    compiled = _compile(
        fn, one_chip, (ring, jnp.float32), (ids, jnp.int32),
        (table, jnp.int32), (table, jnp.float32), (table, jnp.int8),
        ((), jnp.int32))
    assert "while" in compiled.as_text()


@pytest.mark.parametrize("variant,error", [
    (_superstep_lif, "Unsupported cast: uint32 -> float32"),
    (_superstep_iaf, "Only 2D gather is supported"),
])
def test_superstep_kernels_refused_for_v5e_and_by_validate(
        one_chip, monkeypatch, variant, error):
    """The compiler refuses the fused superstep kernels, and on a TPU
    ``EngineConfig`` rejects ``superstep_kernel`` naming that reason."""
    with pytest.raises(Exception, match=re.escape(error)):
        variant(one_chip)
    assert error in cycle.TPU_REFUSAL
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ConfigError) as exc:
        EngineConfig(superstep_kernel=True)
    assert any(v.field == "superstep_kernel" and error in v.problem
               for v in exc.value.violations), exc.value
