"""Single-host reference engine: a thin assembly over the shared window core.

The engine advances the network in *windows* of ``D`` cycles (``D`` = delay
ratio, paper eq. (1)); each cycle is the paper's deliver -> update ->
collocate sequence (Fig. 3):

* ``conventional``: inter-area spikes are delivered every cycle;
* ``structure_aware``: inter-area spikes are *accumulated* for the whole
  window and delivered in one lumped exchange at the window end. Causality
  is guaranteed because every inter-area delay is >= D steps.

Both schedules produce **bit-identical** spike trains: delivery weights live
on an exact 1/256 grid, so f32 ring accumulation is associative-exact, and
the external drive is a counter-based function of absolute model time.

The window/cycle bodies live in :mod:`repro.core.schedule`, shared with the
distributed engine (``dist_engine.py``) and parameterized by an
:class:`repro.core.exchange.Exchange`; this module only resolves the config,
builds the single-host :class:`~repro.core.exchange.LocalExchange`, and jits
the assembled window. The per-cycle *deliver* hot path is backend-selectable
(``EngineConfig.delivery_backend``) -- see :mod:`repro.core.delivery`.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.areas import MultiAreaSpec
from repro.core.connectivity import Network
from repro.core import delivery as delivery_lib
from repro.core import exchange as exchange_lib
from repro.core import faults as faults_lib
from repro.core import neuron as neuron_lib
from repro.core import schedule as schedule_lib
from repro.core.schedule import CONVENTIONAL, STRUCTURE_AWARE, SimState

__all__ = [
    "ConfigError",
    "ConfigViolation",
    "EngineConfig",
    "SimState",
    "Engine",
    "make_engine",
    "CONVENTIONAL",
    "STRUCTURE_AWARE",
]


@dataclasses.dataclass(frozen=True)
class ConfigViolation:
    """One broken EngineConfig rule: which field, what's wrong, how to fix."""

    field: str
    problem: str
    remedy: str

    def __str__(self) -> str:
        return f"{self.field}: {self.problem} [remedy: {self.remedy}]"


class ConfigError(ValueError):
    """All of a config's rule violations in one structured error.

    ``EngineConfig`` used to refuse invalid combinations one raise at a
    time, scattered between ``__post_init__``, ``make_engine`` and
    ``make_dist_engine`` -- fixing a config meant replaying the constructor
    until it stopped throwing. ``EngineConfig.validate()`` now evaluates
    *every* rule and this error carries the full list (``.violations``),
    each with a remedy.
    """

    def __init__(self, violations):
        self.violations: tuple[ConfigViolation, ...] = tuple(violations)
        n = len(self.violations)
        lines = "\n".join(f"  - {v}" for v in self.violations)
        super().__init__(
            f"invalid EngineConfig ({n} rule"
            f"{'s' if n != 1 else ''} violated):\n{lines}")


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    neuron_model: str = "lif"  # 'lif' | 'ignore_and_fire'
    schedule: str = STRUCTURE_AWARE  # 'conventional' | 'structure_aware'
    seed: int = 42
    lif: neuron_lib.LIFParams = dataclasses.field(
        default_factory=neuron_lib.LIFParams
    )
    # The per-cycle deliver hot path: 'onehot' | 'scatter' | 'pallas' |
    # 'event' (see repro.core.delivery). '' defaults to 'onehot'; the
    # `backend` property is the single dispatch point.
    delivery_backend: str = ""
    # How spikes travel between distributed shards (repro.core.exchange):
    # 'dense' (mesh-wide collectives) | 'routed' (connectivity-routed packet
    # rounds over the area-adjacency group graph; structure-aware only).
    # '' resolves to 'local' for the single-host engine and 'dense' for the
    # distributed one.
    exchange: str = ""
    # Distributed event/routed receive tables: True (default) re-cuts the
    # replicated outgoing inter tables into per-shard *inbound* slices
    # (connectivity.shard_inter_tables) so each device stores and scatters
    # only the inter edges it owns (~1/S of the bytes and receive work);
    # False keeps the legacy replicated tables -- the bit-identity
    # reference for the equivalence suite. Single-host engines ignore it.
    shard_inter_tables: bool = True
    # On top of shard_inter_tables, slice each group's inbound inter table
    # over the subgroup (window-within-group) axis as well
    # (connectivity.shard_inter_tables(subgroup=gsz)): the [S, rows, K_in]
    # stack becomes [S, gsz, rows, K_in] and every device lane holds only
    # the rows targeting its own neuron window -- ~gsz x smaller inter
    # slices at identical trajectories (the receive scatter already masks
    # foreign targets to -1). The event path's outgoing intra tables get
    # the same cut (connectivity.slice_intra_tables: [A, n_pad, K_out] ->
    # [gsz, A, n_pad, K_lane]), removing their per-lane replication -- at
    # production scale the dominant per-device table cost. Structure-aware
    # distributed engines only; ignored under shard_inter_tables=False and
    # by the conventional schedule (whose "window" cut is already
    # per-device).
    subgroup_inter_tables: bool = True
    # Use the fused Pallas LIF kernel (kernels.ops.lif_update) for the update
    # phase. None = enable exactly when delivery_backend is 'pallas' (the
    # all-kernel cycle); the flag exists so the fused update can be tested
    # against the jnp chain under every backend.
    fused_update: bool | None = None
    # Event-buffer headroom: s_max = headroom x expected spikes/cycle + slack
    # (cf. NEST's dynamic spike-register resizing; static here). The event
    # path's cost is s_max-bound, so the bound tracks the expected rate;
    # overruns are counted in SimState.overflow.
    s_max_headroom: float = 8.0
    s_max_floor: int = 16
    # Multiplies only the whole-network event bound's constant burst slack
    # (delivery.event_bounds' `4 x floor` term), leaving the per-area bound
    # alone. launch.serve sets this to its fold factor B so a B-copy folded
    # batch keeps the same per-copy burst headroom as B sequential runs --
    # scaling s_max_floor instead would widen every per-area packet B x.
    s_max_burst: int = 1
    # Adaptive two-phase exchange (repro.core.exchange): phase 1 moves a
    # tiny int32 count collective, phase 2 ships packets sized by the
    # smallest power-of-two bucket (>= s_max_floor, pre-compiled ladder) that
    # covers the counted need -- quiet windows ship floor-sized packets, and
    # because the ladder tops out at the hard population cap, a packet can
    # NEVER drop a spike: SimState.overflow is provably 0 and the static
    # s_max_headroom bound becomes irrelevant. Applies wherever id packets
    # exist (event-backend packets on every exchange, the routed global
    # pathway under any backend; the dense bit-packed pathways have nothing
    # to size and are unaffected). Trajectories are bit-identical to the
    # static path whenever the static path itself reports overflow == 0.
    adaptive_exchange: bool = False
    # Fuse the structure-aware window into one D-cycle superstep: blocked
    # ring read/clear (one [.., D] slice per window instead of D dynamic
    # slot updates), D unrolled cycles with window-static slot indices, and a
    # single-pass lumped inter delivery (delivery.deliver_inter_block) in
    # place of the window-end loop of D sequential deliver_inter calls.
    # None = enabled exactly for the structure-aware schedule (the
    # conventional schedule exchanges every cycle, so there is no window to
    # fuse); False forces the legacy per-cycle scan, kept as the semantic
    # reference for the equivalence/overflow suites.
    superstep: bool | None = None
    # Python-unroll the superstep's D cycles (fully static slot indices).
    # Default False: the cycle loop stays a lax.scan over the *live window
    # buffer* (cheap [.., W] column access instead of full-ring updates) --
    # unrolling the jnp graph multiplies the XLA op count ~Dx, which on the
    # CPU backend costs more in per-op dispatch than the static indices
    # save. The fused Pallas kernel (superstep_kernel) always unrolls
    # in-kernel, where the cycles fuse into one VMEM-resident program.
    superstep_unroll: bool = False
    # Run the window body as the fused Pallas superstep kernel
    # (kernels.cycle): membrane state and the live ring slots stay in VMEM
    # across the D unrolled cycles (update + intra delivery fused); the
    # lumped inter exchange still goes through the selected backend.
    # Single-host structure-aware engine only. NOTE on overflow semantics
    # with delivery_backend='event': the kernel's intra delivery is dense
    # (delay-resolved), so the intra packet bound s_max_area does not apply
    # -- intra spikes can neither drop nor count toward SimState.overflow;
    # only the inter packet bound remains. Identical trajectories to the
    # unfused event engine are therefore guaranteed only while the unfused
    # engine reports overflow == 0 (its own exactness condition anyway).
    # Rejected on a TPU: the TPU compiler refuses the kernel
    # (kernels.cycle.TPU_REFUSAL), and it never runs interpreted there.
    superstep_kernel: bool = False
    # Double-buffer the structure-aware window-end exchange
    # (repro.core.exchange start_window_end/finish_window_end): window w's
    # payload collectives are issued at the end of w's compute and their
    # receive scatter deferred to the top of w+1 -- on hardware with async
    # collectives (see launch.simulate.xla_overlap_flags) the transfer
    # overlaps w+1's compute, so the per-window wall tracks
    # max(compute, comm) instead of their sum (the order-statistics claim
    # of sync_model.expected_wall_overlapped). Bit-identical to the
    # sequential schedule: same packets, same scatter values, and the
    # in-flight window drains at every checkpoint/preemption boundary, so
    # saved states ARE sequential states (resume_config_hash treats the
    # flag as layout, not trajectory). Structure-aware schedule only.
    overlap_exchange: bool = False
    # Host-free sharded construction (connectivity.sharded_build_plan /
    # build_shard_tables): the distributed engine generates each device's
    # inbound inter slices and lane-cut intra tables directly from the
    # seeded counter-based connectivity rules
    # (dist_engine.build_network_sharded) instead of slicing a host-built
    # global network -- no process ever materialises the global
    # src_inter/w_inter/delay_inter tensors, so host peak RSS scales with
    # ONE shard's tables, not the model. Bitwise-identical trajectories to
    # the host-build path by the counter-draw row identity
    # (connectivity.draw_pathway_rows); pure layout, not trajectory
    # (resume_config_hash excludes it). Requires the event backend +
    # sharded inbound tables + the structure-aware schedule (the layouts
    # the sharded builders emit); distributed engines only.
    sharded_build: bool = False
    # Host-side fault-injection plan (repro.core.faults.FaultConfig): per-
    # device compute jitter slept at window boundaries, transient
    # checkpoint-write failures, simulated preemption. Consumed by the
    # windowed run loop (schedule.run_windows) only -- nothing here is
    # traced into the jitted window body, so the trajectory is untouched;
    # None injects nothing.
    faults: faults_lib.FaultConfig | None = None

    def __post_init__(self) -> None:
        self.check()

    def validate(
        self, *, distributed: bool | None = None
    ) -> "list[ConfigViolation]":
        """Evaluate *every* config rule and return the full violation list.

        ``distributed=None`` checks the construction-time rules only (the
        set ``__post_init__`` enforces). ``distributed=False`` adds the
        single-host engine's context rules; ``distributed=True`` the
        distributed engine's. The factories pass the dispatch target so a
        bad config surfaces its complete rule list in one structured
        :class:`ConfigError` instead of one raise per constructor replay.
        """
        v: list[ConfigViolation] = []
        if self.neuron_model not in ("lif", "ignore_and_fire"):
            v.append(ConfigViolation(
                "neuron_model",
                f"unknown neuron model {self.neuron_model!r}",
                "use 'lif' or 'ignore_and_fire'"))
        if self.schedule not in (CONVENTIONAL, STRUCTURE_AWARE):
            v.append(ConfigViolation(
                "schedule",
                f"unknown schedule {self.schedule!r}",
                f"use {CONVENTIONAL!r} or {STRUCTURE_AWARE!r}"))
        if self.delivery_backend not in ("",) + delivery_lib.BACKENDS:
            v.append(ConfigViolation(
                "delivery_backend",
                f"unknown delivery_backend {self.delivery_backend!r} "
                f"(expected one of {delivery_lib.BACKENDS})",
                "pick a listed backend, or '' for the default"))
        if self.exchange not in ("",) + exchange_lib.EXCHANGES:
            v.append(ConfigViolation(
                "exchange",
                f"unknown exchange {self.exchange!r} "
                f"(expected one of {exchange_lib.EXCHANGES})",
                "pick a listed exchange, or '' for the default"))
        if self.s_max_burst < 1:
            v.append(ConfigViolation(
                "s_max_burst",
                f"s_max_burst={self.s_max_burst} would shrink the "
                "whole-network event bound's burst slack below its floor",
                "use an integer >= 1 (B for a B-trial folded batch)"))
        if self.exchange == "routed" and self.schedule != STRUCTURE_AWARE:
            v.append(ConfigViolation(
                "exchange",
                "exchange='routed' routes the structure-aware window's "
                "lumped global pathway; the conventional schedule has none",
                "use schedule='structure_aware', or exchange='dense'"))
        if self.superstep is True and self.schedule != STRUCTURE_AWARE:
            v.append(ConfigViolation(
                "superstep",
                "superstep=True requires the structure-aware schedule; "
                "the conventional schedule exchanges every cycle and has "
                "no window to fuse",
                "use schedule='structure_aware', or superstep=None"))
        if self.superstep_kernel:
            if self.schedule != STRUCTURE_AWARE:
                v.append(ConfigViolation(
                    "superstep_kernel",
                    "superstep_kernel fuses the structure-aware window; "
                    "the conventional schedule has no window to fuse",
                    "use schedule='structure_aware'"))
            if self.superstep is False:
                v.append(ConfigViolation(
                    "superstep_kernel",
                    "superstep_kernel=True conflicts with superstep=False",
                    "drop one of the two flags"))
            if jax.default_backend() == "tpu":
                from repro.kernels.cycle import TPU_REFUSAL

                v.append(ConfigViolation(
                    "superstep_kernel", TPU_REFUSAL,
                    "drop superstep_kernel (the jnp superstep fuses the "
                    "same window)"))
        if self.overlap_exchange and self.schedule != STRUCTURE_AWARE:
            v.append(ConfigViolation(
                "overlap_exchange",
                "overlap_exchange double-buffers the structure-aware "
                "window-end exchange; the conventional schedule has no "
                "lumped exchange to overlap",
                "use schedule='structure_aware', or drop overlap_exchange"))
        if self.sharded_build:
            if self.backend != "event":
                v.append(ConfigViolation(
                    "sharded_build",
                    "sharded_build generates the event path's inbound/"
                    "outgoing tables; dense backends read the global "
                    "incoming tensors it never materialises",
                    "use delivery_backend='event'"))
            if not self.shard_inter_tables:
                v.append(ConfigViolation(
                    "sharded_build",
                    "sharded_build emits per-shard inbound inter slices; "
                    "shard_inter_tables=False asks for the replicated "
                    "layout it exists to avoid",
                    "keep shard_inter_tables=True"))
            if self.schedule != STRUCTURE_AWARE:
                v.append(ConfigViolation(
                    "sharded_build",
                    "sharded_build targets the structure-aware placement "
                    "(area groups x subgroup lanes); the conventional "
                    "schedule slices a host-built network",
                    "use schedule='structure_aware'"))
        if distributed is False:
            if self.exchange not in ("", "local"):
                v.append(ConfigViolation(
                    "exchange",
                    f"exchange={self.exchange!r} needs a device mesh; the "
                    "single-host engine is exchange-free "
                    "(use make_dist_engine)",
                    "pass mesh=... to make_simulation, or use exchange=''"))
            if self.sharded_build:
                v.append(ConfigViolation(
                    "sharded_build",
                    "sharded_build is a distributed construction mode; the "
                    "single-host engine holds the whole network anyway "
                    "(use make_dist_engine)",
                    "pass mesh=... to make_simulation"))
        if distributed is True:
            if self.superstep_kernel:
                v.append(ConfigViolation(
                    "superstep_kernel",
                    "superstep_kernel is single-host only; the distributed "
                    "engine fuses the window at the jnp level "
                    "(use_superstep)",
                    "drop superstep_kernel (the jnp superstep fusion is "
                    "the distributed default)"))
        return v

    def check(self, *, distributed: bool | None = None) -> None:
        """Raise :class:`ConfigError` listing every violated rule, if any."""
        violations = self.validate(distributed=distributed)
        if violations:
            raise ConfigError(violations)

    @property
    def backend(self) -> str:
        """The resolved delivery backend ('' defaults to 'onehot')."""
        return self.delivery_backend or "onehot"

    @property
    def fused(self) -> bool:
        """Whether the update phase runs the fused Pallas LIF kernel."""
        if self.fused_update is None:
            return self.backend == "pallas"
        return self.fused_update

    @property
    def use_superstep(self) -> bool:
        """Whether the window runs as one fused D-cycle superstep."""
        if self.schedule != STRUCTURE_AWARE:
            return False
        return True if self.superstep is None else self.superstep


class Engine(NamedTuple):
    init: Callable[[], SimState]
    # Advance one window of D cycles; returns (state', spikes[D, A, n_pad] bool).
    window: Callable[[SimState], tuple[SimState, jax.Array]]
    # Advance n_windows via scan; returns (state', total spikes per window [W]).
    run: Callable[[SimState, int], tuple[SimState, jax.Array]]
    config: EngineConfig
    delay_ratio: int
    # Distributed engines also expose the raw shard_map'd window
    # (state, net, gids) -> (state, block), used by the dry-run to lower with
    # ShapeDtypeStruct connectivity (production scale, no allocation).
    window_raw: Callable | None = None
    # Static mesh-total wire bytes per window of the selected exchange
    # (repro.core.exchange; all zeros for the single-host LocalExchange).
    wire_bytes: dict | None = None
    # Distributed engines: device_put a host/global SimState onto this
    # engine's mesh with the schedule's shardings -- the re-scatter half of
    # checkpoint restore (incl. elastic reshard onto a different group
    # count). None for the single-host engine (restore needs no placement).
    shard_state: Callable | None = None
    # Overlapped pipeline (EngineConfig.overlap_exchange; None otherwise):
    # advance one window while finishing the previous window's in-flight
    # exchange -- (state, InflightWindow) -> (state', InflightWindow',
    # block). `window` stays available as the drained per-window
    # composition (start + immediate finish), bit-identical but unpipelined.
    window_overlap: Callable | None = None
    # Retire an in-flight window: (state, InflightWindow) -> state' with the
    # pending receive scatter applied -- run at checkpoint/preemption/run-end
    # boundaries so the observable state is the sequential trajectory.
    drain: Callable | None = None
    # () -> an empty (scatters-nothing) InflightWindow on this engine's
    # devices: what the pipeline starts from and resets to after a drain.
    init_inflight: Callable | None = None
    # The connectivity the jitted entry points run on, as placed on the
    # device(s): every call passes it to the program as an argument.
    net: Network | None = None


def make_fused_lif_update(params: neuron_lib.LIFParams):
    """An ``(state, i_in, alive) -> (state', spikes)`` closure over the fused
    Pallas kernel, signature-compatible with :func:`repro.core.neuron.lif_update`."""
    from repro.kernels import ops as kops

    kw = dict(
        p11=params.p11, p21=params.p21, p22=params.p22,
        v_th=params.v_th_mv, v_reset=params.v_reset_mv,
        t_ref_steps=params.t_ref_steps,
    )

    def update(state, i_in, alive):
        v, i_syn, refrac, spikes = kops.lif_update(
            state.v, state.i_syn, state.refrac, i_in, alive, **kw)
        return neuron_lib.LIFState(v=v, i_syn=i_syn, refrac=refrac), spikes

    return update


def resolve_params(net: Network, spec: MultiAreaSpec, cfg: EngineConfig):
    """``(lif_params, drive_rate)`` as the engines actually run them.

    The dt-corrected LIF propagators and the per-neuron external drive rate
    ``rate_hz * (ext_rate_hz / 2.5)`` -- the area rate relative to the 2.5 Hz
    reference scales ``spec.ext_rate_hz`` (Fig. 8b heterogeneity), in the
    exact expression the shared update closure uses
    (:func:`repro.core.schedule.make_update_fn`), so the fused superstep
    kernel drives the same math bit-for-bit.
    """
    lif_params = cfg.lif
    if abs(lif_params.dt_ms - net.dt_ms) > 1e-12:
        lif_params = dataclasses.replace(lif_params, dt_ms=net.dt_ms)
    # ShapeDtypeStruct stand-ins (dry-run lowering) carry no data to scale;
    # the eager drive_rate is only consumed by the single-host fused kernel,
    # which always holds a real network.
    drive_rate = (
        net.rate_hz * (spec.ext_rate_hz / 2.5)
        if hasattr(net.rate_hz, "__array__") else None
    )
    return lif_params, drive_rate


def make_fused_superstep(
    net: Network,
    spec: MultiAreaSpec,
    cfg: EngineConfig,
    lif_params: neuron_lib.LIFParams,
    drive_rate: jax.Array,
    gids: jax.Array,
):
    """A ``(neuron_state, fut, t0) -> (state', spikes[D, A, n] bool, fut')``
    closure over the fused Pallas superstep kernel (:mod:`repro.kernels.cycle`).

    The kernel advances all D cycles of a window with membrane state and the
    live window slots VMEM-resident, reproducing the unfused cycle body
    bit-for-bit (same LIF propagators, same counter-based drive, 1/256-grid
    intra deposits). With the event backend the kernel's *dense* intra
    delivery has no packet bound, so equality with the unfused event engine
    holds exactly while that engine reports zero overflow (see the
    ``EngineConfig.superstep_kernel`` note).
    """
    from repro.kernels import ops as kops

    D = net.delay_ratio
    steps_lo = net.steps_lo_intra
    r_span = net.r_span_intra if net.k_intra > 0 else 0

    if cfg.neuron_model == "lif":
        p = lif_params
        drive_p = drive_rate * (net.dt_ms * 1e-3)
        kw = dict(
            p11=p.p11, p21=p.p21, p22=p.p22, v_th=p.v_th_mv,
            v_reset=p.v_reset_mv, t_ref_steps=p.t_ref_steps,
            seed=cfg.seed, w_ext=spec.w_ext,
        )

        def run_lif(neuron_state, fut, t0):
            v, i_syn, refrac, fut, spk = kops.superstep_lif(
                neuron_state.v, neuron_state.i_syn, neuron_state.refrac,
                fut, drive_p, gids, net.alive, net.src_intra, net.w_intra,
                net.delay_intra, t0,
                d_win=D, steps_lo=steps_lo, r_span=r_span, **kw)
            state = neuron_lib.LIFState(v=v, i_syn=i_syn, refrac=refrac)
            return state, jnp.moveaxis(spk, 0, 1) != 0, fut

        return run_lif

    # ignore_and_fire: the same static interval/phase rule as the jnp update.
    interval = neuron_lib.iaf_interval(net.rate_hz, net.dt_ms)

    def run_iaf(neuron_state, fut, t0):
        del t0  # emission is input- and time-base-independent
        cd, fut, spk = kops.superstep_iaf(
            neuron_state.countdown, fut, interval, net.alive,
            net.src_intra, net.w_intra, net.delay_intra,
            d_win=D, steps_lo=steps_lo, r_span=r_span)
        return neuron_lib.IafState(countdown=cd), jnp.moveaxis(spk, 0, 1) != 0, fut

    return run_iaf


def _make_engine(
    net: Network,
    spec: MultiAreaSpec,
    config: EngineConfig = EngineConfig(),
    *,
    gids: jax.Array | None = None,
) -> Engine:
    """Build a jitted reference engine for ``net``.

    The returned callables close over the (host-resident) connectivity; the
    distributed engine in ``dist_engine.py`` shards the same window body
    (:mod:`repro.core.schedule`) over a device mesh.

    ``gids`` overrides the global-id table fed to the counter-based drive
    and the iaf phase rule (default ``arange(A * n_pad)``). The serving
    layer's folded trial batches pass the single-trial ids tiled per copy so
    every copy of the block-diagonal super-network draws the single-trial
    noise stream bit-for-bit.
    """
    D = net.delay_ratio
    A, n_pad = net.alive.shape
    cfg = config
    cfg.check(distributed=False)
    backend = cfg.backend
    if backend == "event" and net.tgt_intra is None:
        raise ValueError("event delivery needs build_network(outgoing=True)")
    lif_params, drive_rate = resolve_params(net, spec, cfg)
    fused_lif = make_fused_lif_update(lif_params) if cfg.fused else None
    if gids is None:
        gids = jnp.arange(A * n_pad, dtype=jnp.int32).reshape(A, n_pad)

    exchange = exchange_lib.LocalExchange(net, cfg)
    update_fn = schedule_lib.make_update_fn(
        cfg, spec, net.dt_ms, lif_params, fused_lif)
    fused_window = (
        make_fused_superstep(net, spec, cfg, lif_params, drive_rate, gids)
        if cfg.superstep_kernel else None
    )
    window_body = schedule_lib.make_window_fn(
        cfg, exchange, update_fn, fused_superstep=fused_window)

    overlap_jit = drain_jit = init_inflight = None
    if cfg.overlap_exchange:
        overlap_body, drain_body = schedule_lib.make_overlap_window_fn(
            cfg, exchange, update_fn, fused_superstep=fused_window)
        overlap_jit = schedule_lib.bind_network(overlap_body, net, gids)
        drain_jit = schedule_lib.bind_network(drain_body, net, gids)

        def init_inflight():
            return exchange.init_inflight(net)

        # The compatibility `window`: one overlapped window drained on the
        # spot -- bit-identical to the sequential window (finish of an empty
        # inflight is a no-op), so every unpipelined caller keeps working.
        def window_body_drained(state, net, gids):
            st, inf, block = overlap_body(
                state, exchange.init_inflight(net), net, gids)
            return drain_body(st, inf, net, gids), block

        window = schedule_lib.bind_network(window_body_drained, net, gids)
    else:
        window = schedule_lib.bind_network(window_body, net, gids)

    def init(seed=None, stim=None) -> SimState:
        """Fresh state; optional per-neuron drive overrides (serving trials).

        ``seed``/``stim`` become ``[A, n_pad]`` SimState leaves consumed by
        the drive in place of / on top of ``cfg.seed`` and the network rate
        (see :class:`repro.core.schedule.SimState`). Scalars broadcast; a
        broadcast scalar seed is bit-identical to the int-seed path. ``None``
        (the default) adds no pytree leaves, so existing state trees,
        checkpoints and shard specs are structurally unchanged.
        """
        if seed is not None or stim is not None:
            if cfg.neuron_model != "lif":
                raise ValueError(
                    "per-trial seed/stim drive the LIF Poisson input; "
                    "ignore_and_fire has no seed or input dependence"
                )
            if cfg.superstep_kernel:
                raise ValueError(
                    "per-trial seed/stim are not supported under "
                    "superstep_kernel (the fused kernel bakes cfg.seed in)"
                )
        if cfg.neuron_model == "lif":
            nstate = neuron_lib.lif_init((A, n_pad))
        else:
            nstate = neuron_lib.ignore_and_fire_init(
                net.alive, net.rate_hz, net.dt_ms, gids
            )
        return SimState(
            neuron=nstate,
            ring=jnp.zeros((A, n_pad, net.ring_len), jnp.float32),
            t=jnp.int32(0),
            spike_count=jnp.zeros((A, n_pad), jnp.int32),
            overflow=jnp.int32(0),
            shipped_bytes=jnp.float32(0),
            seed=(
                None if seed is None
                else jnp.broadcast_to(
                    jnp.asarray(seed, jnp.uint32), (A, n_pad))
            ),
            stim=(
                None if stim is None
                else jnp.broadcast_to(
                    jnp.asarray(stim, jnp.float32), (A, n_pad))
            ),
        )

    if cfg.overlap_exchange:
        # Pipelined scan threading the in-flight window through, drained
        # once at the end -- the jitted fast path actually runs start/finish
        # split across windows, so XLA's latency-hiding scheduler can move
        # the collectives off the critical path.
        def run_body(state: SimState, n_windows: int, net, gids):
            def body(carry, _):
                st, inf = carry
                st, inf, spikes = overlap_body(st, inf, net, gids)
                return (st, inf), spikes.sum(dtype=jnp.int32)

            (state, inf), spikes = jax.lax.scan(
                body, (state, exchange.init_inflight(net)), None,
                length=n_windows)
            return drain_body(state, inf, net, gids), spikes
    else:
        def run_body(state: SimState, n_windows: int, net, gids):
            def body(st, _):
                st, spikes = window_body(st, net, gids)
                return st, spikes.sum(dtype=jnp.int32)

            return jax.lax.scan(body, state, None, length=n_windows)

    run = schedule_lib.bind_network(run_body, net, gids, static_argnums=1)

    return Engine(
        init=init, window=window, run=run, config=cfg, delay_ratio=D,
        wire_bytes=exchange.wire_bytes(net),
        window_overlap=overlap_jit, drain=drain_jit,
        init_inflight=init_inflight, net=net,
    )


def make_engine(
    net: Network,
    spec: MultiAreaSpec,
    config: EngineConfig = EngineConfig(),
    *,
    gids: jax.Array | None = None,
) -> Engine:
    """Deprecated alias for :func:`repro.core.make_simulation`.

    Same engine, same trajectories -- only the entry point moved: the
    unified factory dispatches to this single-host assembly when no mesh is
    given.
    """
    import warnings

    warnings.warn(
        "make_engine is deprecated; use repro.core.make_simulation"
        "(spec, config, net=net) -- it builds the identical single-host "
        "engine when no mesh is given",
        DeprecationWarning,
        stacklevel=2,
    )
    return _make_engine(net, spec, config, gids=gids)
