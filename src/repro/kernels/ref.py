"""Pure-jnp oracles for the Pallas kernels.

These are the semantic ground truth: every kernel test sweeps shapes/dtypes
and asserts allclose (bit-exact for f32 grid weights) against these functions.
They are deliberately written as straight-line jnp with no tiling so they stay
obviously correct.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["lif_update_ref", "spike_deliver_ref"]


def lif_update_ref(
    v: jax.Array,
    i_syn: jax.Array,
    refrac: jax.Array,
    i_in: jax.Array,
    alive: jax.Array,
    *,
    p11: float,
    p21: float,
    p22: float,
    v_th: float,
    v_reset: float,
    t_ref_steps: int,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """One exact-propagator iaf_psc_exp step (oracle for kernels.lif_update).

    Mirrors :func:`repro.core.neuron.lif_update` but takes raw propagator
    scalars so the kernel and the oracle share no code.
    """
    refractory = refrac > 0
    i_new = i_syn * p11 + i_in
    v_prop = v * p22 + i_syn * p21
    v_new = jnp.where(refractory, v_reset, v_prop)
    spikes = (v_new >= v_th) & alive & ~refractory
    v_out = jnp.where(spikes, v_reset, v_new)
    refrac_out = jnp.where(
        spikes, jnp.int32(t_ref_steps), jnp.maximum(refrac - 1, 0)
    )
    return v_out, i_new, refrac_out, spikes


def spike_deliver_ref(
    spikes: jax.Array,   # [N_src] f32 (0/1 spike indicator)
    src: jax.Array,      # [N, K] int32 indices into spikes
    w: jax.Array,        # [N, K] f32 synaptic weights
    delay: jax.Array,    # [N, K] int32 delays (steps)
    *,
    steps_lo: int,
    r_span: int,
) -> jax.Array:
    """Delay-resolved delivery contributions (oracle for kernels.spike_deliver).

    Returns ``contrib[N, r_span]`` with
    ``contrib[n, j] = sum_k w[n,k] * spikes[src[n,k]] * [delay[n,k] == steps_lo + j]``.

    The engine adds ``contrib[:, j]`` into ring slot ``(t + steps_lo + j) % R``.
    """
    vals = w * spikes[src]  # [N, K]
    j = delay - steps_lo    # [N, K], target slot offset
    onehot = jax.nn.one_hot(j, r_span, dtype=vals.dtype)  # [N, K, r_span]
    return jnp.einsum("nk,nkr->nr", vals, onehot,
                      precision=jax.lax.Precision.HIGHEST)
