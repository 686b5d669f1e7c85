"""The shared window/cycle core: one deliver -> update -> collocate body.

Before this module the single-host engine (``engine.py``) and the distributed
engine (``dist_engine.py``) each carried their own copy of the window
machinery -- per-cycle scan, fused D-cycle superstep, legacy window + lumped
exchange -- ~400 lines of drift-prone duplication. Both engines now assemble
the *same* window body from here, parameterized by an
:class:`repro.core.exchange.Exchange`:

* what happens *inside* a cycle (ring read, neuron update, spike counting)
  and *around* a window (blocked ring open/merge, superstep scan vs unroll,
  the legacy per-cycle reference) lives here, once;
* *how spikes travel* -- single-host identity, dense mesh collectives, or
  connectivity-routed packets -- lives in the exchange object.

The schedules (paper Fig. 3):

* ``conventional``: the long-range pathway is exercised every cycle
  (``inter_now=True`` in the cycle hook);
* ``structure_aware``: long-range spikes accumulate for the whole window and
  travel once, in the window-end hook. Causal because every inter-area delay
  is >= D steps; bit-identical because delivery weights live on the exact
  1/256 grid.

Every variant produces bit-identical spike trains; the equivalence suites
(tests/test_system.py, tests/test_distributed.py, tests/test_exchange.py)
pin that across schedules, backends, exchanges and meshes.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import faults as faults_lib
from repro.core import neuron as neuron_lib
from repro.core import partition as partition_lib
from repro.core import ring_buffer

__all__ = [
    "CONVENTIONAL",
    "STRUCTURE_AWARE",
    "SCOPES",
    "SimState",
    "SimCheckpointer",
    "RunResult",
    "bind_network",
    "make_update_fn",
    "make_window_fn",
    "make_overlap_window_fn",
    "restore_sim",
    "resume_config_hash",
    "run_windows",
]

CONVENTIONAL = "conventional"
STRUCTURE_AWARE = "structure_aware"

# The window program's named scopes (``jax.named_scope``): every op of a
# window lies under one of them but the loops' plumbing and the stacking of
# the window's spike raster, so a profiler trace groups the device time by
# layer. An op belongs to the innermost of these names in its ``op_name``.
# Scopes are compile-time metadata; the compiled program is unchanged.
SCOPES = ("neuron_update", "intra_deliver", "inter_exchange", "ring")
NEURON_UPDATE, INTRA_DELIVER, INTER_EXCHANGE, RING = SCOPES


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SimState:
    neuron: Any               # LIFState or IafState pytree
    ring: jax.Array           # [A, n_pad, R]
    t: jax.Array              # scalar int32, absolute cycle index
    spike_count: jax.Array    # [A, n_pad] int32 cumulative spikes
    # Scalar int32: spikes dropped because a fixed-size packet (event
    # backend, or a routed-exchange edge) exceeded its static s_max bound
    # (0 on the dense pathways; any nonzero value means the run is no longer
    # exact and s_max_headroom/floor must be raised). Under the adaptive
    # two-phase exchange (EngineConfig.adaptive_exchange) this is provably
    # always 0: phase-1 counts size every packet and the bucket ladders top
    # out at the hard population cap.
    overflow: Any = None
    # Scalar f32: cumulative mesh-total wire bytes the exchanges actually
    # shipped (counts + payloads). Static packets add their fixed byte
    # constants; adaptive packets add the bytes of the bucket each window
    # actually selected -- the *measured* counterpart of the static
    # worst-case accounting in Engine.wire_bytes / exchange.wire_report
    # (f32: byte totals overflow int32 long before they lose f32 precision
    # that matters for reporting).
    shipped_bytes: Any = None
    # Optional per-neuron drive seed ([A, n_pad] uint32) -- the serving
    # layer's trial axis: a folded batch of trials carries each trial's seed
    # on its own block of neurons, and the counter-based drive reads it
    # instead of the engine-wide EngineConfig.seed. None (the default)
    # contributes no pytree leaf, so every pre-serving state, checkpoint
    # manifest and shard_map spec tree is structurally unchanged; a
    # broadcast scalar equal to cfg.seed is bit-identical to None.
    seed: Any = None
    # Optional per-neuron stimulus scale ([A, n_pad] f32) multiplying the
    # external drive rate -- the per-trial stimulus knob of a serving
    # request. None contributes no leaf; an all-ones array is bit-identical
    # to None (x * 1.0f is exact).
    stim: Any = None


def bind_network(fn: Callable, net, gids, *, static_argnums=()) -> Callable:
    """``jit(fn)`` with the trailing ``(net, gids)`` bound: ``f(*args) ->
    fn(*args, net, gids)``.

    The connectivity enters the program as *arguments*. Closed over, jit
    would bake every synapse table into the executable as a constant --
    gigabytes at chip scale, re-embedded for every compiled variant.
    """
    jitted = jax.jit(fn, static_argnums=static_argnums)

    def bound(*args):
        return jitted(*args, net, gids)

    # The program as compiled, for reading its HLO and scopes:
    # ``bound.lower(*args).compile().as_text()``.
    bound.lower = lambda *args: jitted.lower(*args, net, gids)
    return bound


def make_update_fn(
    cfg,                       # EngineConfig (duck-typed to avoid a cycle)
    spec,                      # MultiAreaSpec
    dt_ms: float,
    lif_params,
    fused_lif: Callable | None,
) -> Callable:
    """The neuron-update closure shared by both engines.

    ``update(neuron_state, i_in, t, net_view, gids, seed=None, stim=None) ->
    (state', spikes)`` where ``net_view`` may be the full network (single
    host) or a shard_map view -- the drive uses the view's
    ``rate_hz``/``alive`` and the *global* ids in ``gids``, so any sharding
    sees bit-identical noise. The drive rate is
    ``rate_hz * (ext_rate_hz / 2.5)`` -- one expression everywhere (the
    engines previously used two algebraically-equal-but-ULP-different forms;
    the shared core makes the cross-engine bit-equality structural instead
    of coincidental).

    ``seed``/``stim`` are the per-trial drive leaves of ``SimState`` (the
    serving layer's trial axis): ``seed`` replaces ``cfg.seed`` in the
    counter-based drive and ``stim`` scales the drive rate. ``None`` (every
    pre-serving caller) keeps the classic expressions verbatim.
    """
    drive_scale = spec.ext_rate_hz / 2.5

    @jax.named_scope(NEURON_UPDATE)
    def update(neuron_state, i_in, t, net, gids, seed=None, stim=None):
        if cfg.neuron_model == "lif":
            rate = net.rate_hz * drive_scale
            if stim is not None:
                rate = rate * stim
            drive = neuron_lib.poisson_drive(
                cfg.seed if seed is None else seed, t, gids, rate, dt_ms,
                spec.w_ext,
            )
            if fused_lif is not None:
                return fused_lif(neuron_state, i_in + drive, net.alive)
            return neuron_lib.lif_update(
                neuron_state, i_in + drive, net.alive, lif_params)
        return neuron_lib.ignore_and_fire_update(
            neuron_state, i_in, net.alive, net.rate_hz, dt_ms)

    return update


def make_window_fn(
    cfg,
    exchange,
    update_fn: Callable,
    *,
    fused_superstep: Callable | None = None,
) -> Callable:
    """Build the ``window(state, net, gids) -> (state', block)`` body.

    ``net``/``gids`` may be full arrays (single-host) or shard_map views
    (distributed) -- all communication is delegated to ``exchange``:

    * ``exchange.cycle(ring, spikes, t, net, gids, inter_now=...)`` runs the
      per-cycle short-range pathway (and, under the conventional schedule,
      the per-cycle long-range exchange too);
    * ``exchange.window_end(ring, block, t0, net, gids, blocked=...)`` runs
      the structure-aware schedule's lumped window-end exchange.

    During a superstep, ``ring`` handed to the cycle hook is the *live
    window buffer* and ``t`` the within-window slot index -- deposits are
    wrap-free by construction (``Network.live_window``), so the same
    delivery code serves both modes.

    ``fused_superstep`` (single-host only) replaces the whole in-window loop
    with the fused Pallas superstep kernel; the lumped exchange still goes
    through the exchange hook.
    """

    compute_window = _make_compute_window(
        cfg, exchange, update_fn, fused_superstep)

    if cfg.schedule == CONVENTIONAL:
        return compute_window

    blocked = bool(cfg.use_superstep)

    def window(state: SimState, net, gids):
        t0 = state.t
        state, block = compute_window(state, net, gids)
        # The lumped 'global communication': the whole [D, ...] block in
        # one pass. Every inter-area delay is >= D, so slot (t0+s+d) is
        # strictly in the future of the window -- causal (paper §2.1)
        # and bit-identical to D per-cycle deliveries.
        with jax.named_scope(INTER_EXCHANGE):
            ring, d_over, d_ship = exchange.window_end(
                state.ring, block, t0, net, gids, blocked=blocked)
        return dataclasses.replace(
            state, ring=ring, overflow=state.overflow + d_over,
            shipped_bytes=state.shipped_bytes + d_ship), block

    return window


def _make_compute_window(cfg, exchange, update_fn, fused_superstep):
    """The window body *without* the structure-aware window-end exchange.

    Shared by the sequential window (which appends ``exchange.window_end``)
    and the overlapped window (which brackets it with ``finish``/``start``);
    under the conventional schedule this IS the whole window (the per-cycle
    hook runs the global pathway too).
    """

    def compute_window(state: SimState, net, gids):
        D = net.delay_ratio
        t0 = state.t

        def cycle_state(st: SimState, inter_now: bool):
            """One deliver -> update -> collocate cycle on full SimState."""
            with jax.named_scope(RING):
                i_in, ring = ring_buffer.read_and_clear(st.ring, st.t)
            nstate, spikes = update_fn(
                st.neuron, i_in, st.t, net, gids, seed=st.seed, stim=st.stim)
            with jax.named_scope(INTRA_DELIVER):
                ring, over, shipped = exchange.cycle(
                    ring, spikes, st.t, net, gids, inter_now=inter_now)
                over = st.overflow + over
                shipped = st.shipped_bytes + shipped
            with jax.named_scope(NEURON_UPDATE):
                spike_count = st.spike_count + spikes.astype(jnp.int32)
            return dataclasses.replace(
                st,
                neuron=nstate,
                ring=ring,
                t=st.t + 1,
                spike_count=spike_count,
                overflow=over,
                shipped_bytes=shipped,
            ), spikes

        if cfg.schedule == CONVENTIONAL:
            # Global exchange (and hence long-range delivery) every cycle.
            def body(st, _):
                return cycle_state(st, inter_now=True)

            return jax.lax.scan(body, state, None, length=D)

        if cfg.use_superstep:
            # One fused D-cycle superstep: the window's D input slots are one
            # contiguous ring block (phase alignment: t0 ≡ 0 mod D and
            # ring_len ≡ 0 mod D), read and cleared once; cycles consume
            # window-static columns of the live buffer ``fut``.
            W = net.live_window
            with jax.named_scope(RING):
                fut, ring = ring_buffer.open_window(state.ring, t0, D, W)
            carry = (state.neuron, fut, state.overflow, state.shipped_bytes)

            def step(carry, s):
                """One cycle of the superstep on the live window ``fut``."""
                neuron, fut, over, shipped = carry
                with jax.named_scope(RING):
                    i_in = fut[..., s]
                neuron, spikes = update_fn(
                    neuron, i_in, t0 + s, net, gids,
                    seed=state.seed, stim=state.stim)
                with jax.named_scope(INTRA_DELIVER):
                    fut, d_over, d_ship = exchange.cycle(
                        fut, spikes, s, net, gids, inter_now=False)
                    over, shipped = over + d_over, shipped + d_ship
                return (neuron, fut, over, shipped), spikes

            if fused_superstep is not None:
                neuron, block, fut = fused_superstep(state.neuron, fut, t0)
                over, shipped = state.overflow, state.shipped_bytes
            elif cfg.superstep_unroll:
                cols = []
                for s in range(D):  # unrolled: s static, slot math vanishes
                    carry, spikes = step(carry, s)
                    cols.append(spikes)
                (neuron, fut, over, shipped), block = carry, jnp.stack(cols)
            else:
                # Scan over the live window: slot access touches only the
                # small [.., W] buffer (wrap-free), never the ring.
                (neuron, fut, over, shipped), block = jax.lax.scan(
                    step, carry, jnp.arange(D, dtype=jnp.int32))
            with jax.named_scope(RING):
                ring = ring_buffer.merge_window_tail(
                    ring, fut[..., D:], t0 + D)
            with jax.named_scope(NEURON_UPDATE):
                spike_count = (state.spike_count
                               + block.astype(jnp.int32).sum(0))
            return dataclasses.replace(
                state,
                neuron=neuron,
                ring=ring,
                t=t0 + D,
                spike_count=spike_count,
                overflow=over,
                shipped_bytes=shipped,
            ), block

        # Legacy structure-aware window (the semantic reference for the
        # superstep): per-cycle scan, the window-end exchange appended by
        # the caller.
        def body(st, _):
            return cycle_state(st, inter_now=False)

        return jax.lax.scan(body, state, None, length=D)

    return compute_window


def make_overlap_window_fn(
    cfg,
    exchange,
    update_fn: Callable,
    *,
    fused_superstep: Callable | None = None,
) -> tuple[Callable, Callable]:
    """Build the double-buffered window pair ``(window_overlap, drain)``.

    ``window_overlap(state, inflight, net, gids) -> (state', inflight',
    block)`` runs one window of the overlapped pipeline: it first *finishes*
    the previous window's in-flight exchange (the collective-free receive
    scatter -- its earliest deposit lands exactly on the first ring slot
    this window reads, so it cannot be deferred further), then runs the
    compute body, then *starts* this window's exchange (assembly + all
    collectives), handing the received payload back as the new in-flight
    state. On hardware with async collectives the start's transfers overlap
    the next window's compute; the schedule's wall becomes
    ``max(compute, comm)`` per window instead of their sum.

    ``drain(state, inflight, net, gids) -> state'`` retires an in-flight
    window at a pipeline boundary (checkpoint, preemption, end of run) so
    the ring equals the sequential schedule's -- a drained pipeline is
    bitwise the sequential trajectory, which is what keeps checkpoints
    layout-free and resume exact.
    """
    if cfg.schedule == CONVENTIONAL:
        raise ValueError(
            "overlap_exchange requires the structure-aware schedule: the "
            "conventional schedule has no lumped window-end exchange to "
            "overlap with compute")
    compute_window = _make_compute_window(
        cfg, exchange, update_fn, fused_superstep)
    blocked = bool(cfg.use_superstep)

    def window_overlap(state: SimState, inflight, net, gids):
        with jax.named_scope(INTER_EXCHANGE):
            ring = exchange.finish_window_end(
                state.ring, inflight, net, gids, blocked=blocked)
        state = dataclasses.replace(state, ring=ring)
        t0 = state.t
        state, block = compute_window(state, net, gids)
        with jax.named_scope(INTER_EXCHANGE):
            inflight, d_over, d_ship = exchange.start_window_end(
                block, t0, net, gids, blocked=blocked)
        return dataclasses.replace(
            state, overflow=state.overflow + d_over,
            shipped_bytes=state.shipped_bytes + d_ship), inflight, block

    def drain(state: SimState, inflight, net, gids):
        with jax.named_scope(INTER_EXCHANGE):
            ring = exchange.finish_window_end(
                state.ring, inflight, net, gids, blocked=blocked)
        return dataclasses.replace(state, ring=ring)

    return window_overlap, drain


# ---------------------------------------------------------------------------
# Windowed checkpoint / resume / fault-tolerant run loop
# ---------------------------------------------------------------------------
#
# Checkpoints are only taken at *window boundaries*: there t ≡ 0 (mod D), the
# live window buffer is merged back and the ring's phase alignment
# (ring_len ≡ 0 mod D) is the same invariant a fresh init satisfies, so a
# restored SimState re-enters the superstep exactly where an uninterrupted
# run would. The external drive is a counter-based pure function of
# (seed, t, gid) -- the "RNG state" is fully captured by recording the seed
# and the absolute cycle index t in the manifest -- which is what makes
# resume *bitwise* identical rather than statistically identical.
#
# State arrays are keyed by area in global layout ([A, n_pad, ...]), so a
# checkpoint gathered to host memory is mesh-independent: restoring onto a
# different group count is gather -> (re-order per the elastic reshard plan,
# the identity for contiguous plans) -> re-scatter through the new engine's
# shardings, while the distributed factory (make_simulation with a mesh)
# re-cuts the inter receive tables for the new mesh via
# connectivity.shard_inter_tables.


# Config fields that are *layout*, not *trajectory*: every value produces
# bit-identical spike trains (sharded inter tables are re-cut by the
# distributed factory for whatever mesh the resume runs on; a drained overlap
# pipeline IS the sequential trajectory; a sharded build regenerates the
# exact same tables from the counter-based rules a host build draws), so
# checkpoints must stay exchangeable across them. Recorded in the manifest
# payload for forensics, excluded from the compatibility hash and the
# mismatch diff.
_LAYOUT_KEYS = frozenset(
    {"shard_inter_tables", "subgroup_inter_tables", "overlap_exchange",
     "sharded_build"})


def resume_config_hash(cfg, net, *, exchange: str | None = None):
    """``(hash, payload)`` identifying what a checkpoint can resume into.

    Covers everything that changes the *trajectory* (neuron model, schedule,
    exchange, adaptive flag, delivery backend, seed, packet bounds) plus the
    network invariants a SimState's shapes encode (D, ring length, area
    grid). Deliberately excludes the mesh shape: elastic reshard-restart
    resumes the same config on a different group count. Layout-only fields
    (``_LAYOUT_KEYS``: replicated vs sharded inter tables, overlapped vs
    sequential exchange) ride along in the payload but do not enter the
    hash -- they change how the run executes, never what it computes.
    ``exchange`` overrides ``cfg.exchange`` so launchers can hash the
    requested exchange independently of how it resolves for the current
    device count.
    """
    payload = {
        "neuron_model": cfg.neuron_model,
        "schedule": cfg.schedule,
        "exchange": cfg.exchange if exchange is None else exchange,
        "adaptive_exchange": bool(cfg.adaptive_exchange),
        "delivery_backend": cfg.backend,
        "seed": int(cfg.seed),
        "s_max_headroom": float(cfg.s_max_headroom),
        "s_max_floor": int(cfg.s_max_floor),
        "delay_ratio": int(net.delay_ratio),
        "ring_len": int(net.ring_len),
        "n_areas": int(net.n_areas),
        "n_pad": int(net.n_pad),
        "shard_inter_tables": bool(cfg.shard_inter_tables),
        "subgroup_inter_tables": bool(
            getattr(cfg, "subgroup_inter_tables", True)),
        "overlap_exchange": bool(getattr(cfg, "overlap_exchange", False)),
        "sharded_build": bool(getattr(cfg, "sharded_build", False)),
    }
    hashed = {k: v for k, v in payload.items() if k not in _LAYOUT_KEYS}
    digest = hashlib.sha256(
        json.dumps(hashed, sort_keys=True).encode()).hexdigest()[:16]
    return digest, payload


class SimCheckpointer:
    """Windowed SimState checkpointing through ``checkpoint.AsyncWriter``.

    ``save`` submits the full SimState pytree (neuron state, phase-aligned
    rings, ``t``, ``spike_count``, ``overflow``, ``shipped_bytes``) with a
    manifest recording the window phase, seed (the drive's RNG state), the
    group count the run executed on, and the resume-config hash. The step id
    is the count of *completed windows* (``t // D``), so ``latest_step`` is
    directly "how far did the dead run get".
    """

    def __init__(
        self,
        directory: str,
        engine,
        net,
        *,
        every: int = 50,
        keep: int = 3,
        exchange: str | None = None,
        n_groups: int = 1,
        injector: faults_lib.FaultInjector | None = None,
        retries: int = 3,
        backoff_s: float = 0.05,
    ):
        from repro.checkpoint import manager as ckpt_manager

        self.directory = directory
        self.every = every
        self.delay_ratio = int(engine.delay_ratio)
        self.seed = int(engine.config.seed)
        self.n_groups = int(n_groups)
        self.config_hash, self.config_payload = resume_config_hash(
            engine.config, net, exchange=exchange)
        save_fn = None
        if injector is not None and injector.cfg.ckpt_write_failures > 0:
            save_fn = injector.wrap_save(ckpt_manager.save)
        self.writer = ckpt_manager.AsyncWriter(
            directory, keep=keep, retries=retries, backoff_s=backoff_s,
            save_fn=save_fn)
        self.saved_windows: list[int] = []

    def due(self, window: int) -> bool:
        """Does the cadence fire at this completed-window count? Callers
        running the overlapped pipeline check this *before* touching the
        state so the in-flight window can drain first (the save must see
        the sequential-equivalent ring for resume to stay bitwise)."""
        w = int(window)
        return self.every > 0 and w > 0 and w % self.every == 0

    def maybe_save(self, state: SimState, window: int | None = None) -> int | None:
        """Cadence hook: save when the completed-window count hits `every`.

        Pass ``window`` (the caller's host-side completed-window count) to
        keep the off-cadence path free of device syncs -- reading
        ``state.t`` forces a transfer every window, which is exactly the
        overhead budget checkpointing must not spend.
        """
        w = int(state.t) // self.delay_ratio if window is None else int(window)
        if self.due(w):
            return self.save(state)
        return None

    def save(self, state: SimState) -> int:
        """Submit a window-boundary checkpoint; returns the step id."""
        t = int(state.t)
        if t % self.delay_ratio != 0:
            raise ValueError(
                f"checkpoint requested mid-window (t={t}, D="
                f"{self.delay_ratio}): only window boundaries keep the ring "
                f"phase alignment a resumed superstep needs")
        w = t // self.delay_ratio
        if self.saved_windows and self.saved_windows[-1] == w:
            return w  # boundary already checkpointed (cadence + preemption)
        ring_len = int(state.ring.shape[-1])
        extra = {
            "kind": "simstate",
            "t": t,
            "window": w,
            "window_phase": 0,
            "delay_ratio": self.delay_ratio,
            "ring_len": ring_len,
            "ring_phase": t % ring_len,
            "seed": self.seed,
            "n_groups": self.n_groups,
            "config_hash": self.config_hash,
            "config": self.config_payload,
        }
        self.writer.submit(w, state, extra=extra)
        self.saved_windows.append(w)
        return w

    @property
    def retry_count(self) -> int:
        return self.writer.retry_count

    def close(self) -> None:
        self.writer.close()


def _permute_areas(state: SimState, order: np.ndarray) -> SimState:
    """Re-order the per-area leading axis of every area-keyed leaf."""
    n_areas = int(state.spike_count.shape[0])
    idx = jnp.asarray(order, dtype=jnp.int32)

    def permute(x):
        if hasattr(x, "ndim") and x.ndim >= 2 and x.shape[0] == n_areas:
            return jnp.take(x, idx, axis=0)
        return x

    return jax.tree.map(permute, state)


def restore_sim(
    directory: str,
    engine,
    net,
    *,
    step: int | None = None,
    exchange: str | None = None,
    n_groups: int = 1,
):
    """Restore a SimState checkpoint into ``engine``, resharding if needed.

    Fails fast -- before any array is materialised -- when the checkpoint's
    resume-config hash differs from the current run's (clear field-by-field
    error instead of a deep shape mismatch), or when its recorded window
    phase is unaligned. If the checkpoint was taken on a different group
    count, the elastic reshard plan
    (:func:`repro.core.partition.elastic_reshard_plan`) validates the
    re-mesh, the per-area state rows are re-ordered per the plan (identity
    for contiguous plans), and the new engine's ``shard_state`` re-scatters
    them over the new mesh. Returns ``(state, info)`` where ``info`` carries
    the manifest, resumed step and reshard accounting.
    """
    from repro.checkpoint import manager as ckpt_manager

    manifest, step = ckpt_manager.read_manifest(directory, step)
    extra = manifest.get("extra", {})
    expect_hash, payload = resume_config_hash(
        engine.config, net, exchange=exchange)
    got_hash = extra.get("config_hash")
    if got_hash is not None and got_hash != expect_hash:
        old = extra.get("config", {})
        diffs = [
            f"  {k}: checkpoint={old.get(k)!r} != run={v!r}"
            for k, v in payload.items()
            if k not in _LAYOUT_KEYS and old.get(k) != v
        ] or [f"  config hash {got_hash} != {expect_hash}"]
        raise ValueError(
            "checkpoint is incompatible with this run's config -- resuming "
            "would not reproduce the uninterrupted trajectory:\n"
            + "\n".join(diffs))
    if extra.get("window_phase", 0) != 0:
        raise ValueError(
            f"checkpoint at step {step} is not window-phase aligned "
            f"(window_phase={extra.get('window_phase')}); only "
            f"window-boundary checkpoints can resume the D-cycle superstep")

    state, _ = ckpt_manager.restore(directory, like=engine.init(), step=step)

    old_groups = int(extra.get("n_groups", n_groups))
    reshard_info = None
    if n_groups != old_groups:
        sizes = np.asarray(net.alive).sum(axis=1).astype(int)
        placement = partition_lib.placement_from_sizes(
            sizes, old_groups, n_pad=int(net.n_pad))
        # Raises (fail fast) when the areas cannot rebalance onto n_groups.
        plan = partition_lib.elastic_reshard_plan(placement, n_groups)
        order = partition_lib.reshard_area_order(plan)
        if not np.array_equal(order, np.arange(order.size)):
            state = _permute_areas(state, order)
        reshard_info = {
            "old_n_groups": old_groups,
            "new_n_groups": n_groups,
            "moved_areas": partition_lib.reshard_moves(plan),
        }
    if engine.shard_state is not None:
        state = engine.shard_state(state)
    return state, {"step": step, "manifest": manifest,
                   "reshard": reshard_info}


@dataclasses.dataclass
class RunResult:
    """Outcome of :func:`run_windows` (also returned inside ``Preempted``)."""

    state: SimState
    spikes_per_window: np.ndarray   # [windows_done] int64
    window_times_s: np.ndarray      # wall per window, incl. injected jitter
    windows_done: int               # completed in THIS call
    injected_sleep_s: float = 0.0
    overlapped: bool = False        # ran the double-buffered pipeline
    drains: int = 0                 # in-flight windows retired at boundaries


def run_windows(
    engine,
    state: SimState,
    n_windows: int,
    *,
    checkpointer: SimCheckpointer | None = None,
    faults: "faults_lib.FaultConfig | faults_lib.FaultInjector | None" = None,
    on_window: Callable[[int, SimState], None] | None = None,
    on_block: Callable[[int, Any], None] | None = None,
    stop_requested: Callable[[], bool] | None = None,
) -> RunResult:
    """The engines' resilient run loop: windowed, checkpointed, fault-aware.

    ``Engine.run`` is the fast path -- one jitted scan, no host control in
    between. This loop trades one dispatch per window for window-boundary
    control, which is exactly where checkpoints are phase-safe: after every
    window it blocks on the state, submits a checkpoint when the cadence
    fires, injects configured faults, and stops SIGTERM-style on simulated
    preemption or when ``stop_requested()`` turns true (a real signal
    handler's flag) -- writing a final checkpoint first, then raising
    :class:`repro.core.faults.Preempted` with the result attached as
    ``exc.result``. Works unchanged for the single-host and distributed
    engines -- both assemble their window from this module.

    When the engine carries the overlapped pipeline (``engine.window_overlap``
    is set), the loop threads the in-flight window through and *drains* it at
    every pipeline boundary -- before a checkpoint save, on preemption/stop,
    and at the end of the run -- so everything observable (saved state,
    returned state) is the sequential-equivalent trajectory. Injected faults
    then model the pipeline: the sequential loop sleeps ``compute + comm``
    per window, the overlapped loop ``max(compute, prev window's comm)``
    with the last window's comm paid at the drain -- the realized sleeps ARE
    the order-statistics quantities ``sync_model.expected_wall_overlapped``
    prices.

    ``faults`` defaults to ``engine.config.faults``; pass an injector to
    share fault state (e.g. the transient-write budget also wired into the
    checkpointer) across resume legs.

    ``on_window(w, state)`` fires after every window; under the overlapped
    pipeline ``state`` may still have an undrained in-flight window (its
    ``spike_count``/``t`` are exact, the ring is missing the last window's
    inter deposits).

    ``on_block(w, block)`` is the per-request streaming cadence hook the
    serving layer hangs its result plumbing on: it fires after every window
    with the window's raw ``[D, A, n_pad]`` bool spike block (exact even
    when the overlap pipeline has an undrained exchange in flight -- the
    block is this window's own emissions). A multi-tenant batch slices each
    trial's rows out of the block and finalises a request the moment its
    own duration is reached, independent of the batch's longest trial.

    The host work of each window carries a profiler span
    (``jax.profiler.TraceAnnotation``, ~1 us when no trace runs):
    ``repro.window.dispatch``, ``repro.window.wait``,
    ``repro.window.spike_count`` and, when due, ``repro.window.checkpoint``.
    The callbacks carry none; a caller names its own.
    """
    fault_arg = faults if faults is not None else getattr(
        engine.config, "faults", None)
    if isinstance(fault_arg, faults_lib.FaultInjector):
        injector = fault_arg
    elif fault_arg is not None and fault_arg.any_enabled:
        injector = faults_lib.FaultInjector(
            fault_arg, n_devices=jax.device_count(),
            delay_ratio=engine.delay_ratio)
    else:
        injector = None

    overlapped = getattr(engine, "window_overlap", None) is not None
    inflight = engine.init_inflight() if overlapped else None
    in_flight_dirty = False
    pending_comm = 0.0
    drains = 0

    D = int(engine.delay_ratio)
    w_done = int(jax.device_get(state.t)) // D  # absolute windows completed
    spikes: list[int] = []
    times: list[float] = []
    slept = 0.0

    def result() -> RunResult:
        return RunResult(
            state=state,
            spikes_per_window=np.asarray(spikes, dtype=np.int64),
            window_times_s=np.asarray(times, dtype=np.float64),
            windows_done=len(times),
            injected_sleep_s=slept,
            overlapped=overlapped,
            drains=drains,
        )

    def drain_pipeline():
        """Retire the in-flight window (and pay its modelled comm time)."""
        nonlocal state, inflight, in_flight_dirty, pending_comm, slept, drains
        if not overlapped or not in_flight_dirty:
            return
        state = engine.drain(state, inflight)
        inflight = engine.init_inflight()
        jax.block_until_ready(state.ring)
        if injector is not None and pending_comm > 0.0:
            slept += injector.inject(pending_comm)
        pending_comm = 0.0
        in_flight_dirty = False
        drains += 1

    for _ in range(n_windows):
        t0 = time.perf_counter()
        with TraceAnnotation("repro.window.dispatch"):
            if overlapped:
                state, inflight, block = engine.window_overlap(
                    state, inflight)
                in_flight_dirty = True
            else:
                state, block = engine.window(state)
        with TraceAnnotation("repro.window.wait"):
            jax.block_until_ready(state.ring)
        w_done += 1
        if injector is not None:
            comp = injector.window_jitter_s(w_done)
            comm = injector.window_comm_jitter_s(w_done)
            if overlapped:
                # This window's compute straggler overlaps the *previous*
                # window's exchange; its own exchange becomes next window's
                # in-flight time.
                slept += injector.inject(max(comp, pending_comm))
                pending_comm = comm
            else:
                slept += injector.inject(comp + comm)
        times.append(time.perf_counter() - t0)
        with TraceAnnotation("repro.window.spike_count"):
            spikes.append(int(np.asarray(jnp.sum(block.astype(jnp.int32)))))
        if on_block is not None:
            on_block(w_done, block)
        if checkpointer is not None and checkpointer.due(w_done):
            with TraceAnnotation("repro.window.checkpoint"):
                drain_pipeline()
                checkpointer.maybe_save(state, window=w_done)
        if on_window is not None:
            on_window(w_done, state)
        stop = stop_requested is not None and stop_requested()
        if (injector is not None and injector.preempt_now(w_done)) or stop:
            drain_pipeline()
            path = None
            if checkpointer is not None:
                checkpointer.save(state)   # the SIGTERM-grace checkpoint
                checkpointer.close()
                path = checkpointer.directory
            exc = faults_lib.Preempted(w_done, path)
            exc.result = result()
            raise exc
    drain_pipeline()
    return result()
