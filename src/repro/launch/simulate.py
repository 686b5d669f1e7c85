"""Production SNN simulation launcher (the paper's state-propagation driver).

    PYTHONPATH=src python -m repro.launch.simulate --model mam --scale 0.002 \
        --t-ms 500 --schedule structure_aware --backend event

Runs on whatever devices exist: a single device uses the reference engine; a
multi-device mesh (e.g. under XLA_FLAGS=--xla_force_host_platform_device_count=8
or on real TPU pods) uses the distributed two-tier engine, with the global
pathway selected by ``--exchange`` (``dense`` mesh-wide collectives vs the
connectivity-``routed`` packet rounds of ``repro.core.exchange``). Reports
per-window wall time, spike statistics, wire bytes per window (static worst
case AND the measured ``SimState.shipped_bytes``), and -- with
``--compare`` -- verifies the conventional and structure-aware schedules
produce identical spikes. ``--adaptive`` switches every packet onto the
adaptive two-phase exchange (counts first, then bucket-sized payloads;
overflow is asserted zero); ``--compare-adaptive`` additionally verifies
the adaptive and static paths are bit-identical.
"""

from __future__ import annotations

import argparse
import importlib.util
import os
import signal
import time

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.areas import mam_benchmark_spec, mam_spec
from repro.core.connectivity import area_adjacency, build_network
from repro.core.engine import EngineConfig
from repro.core.factory import make_simulation
from repro.core import exchange as exchange_lib
from repro.core import faults as faults_lib
from repro.core import schedule as schedule_lib

# Where ``--profile`` writes its trace (relative to the working directory).
PROFILE_DIR = "simulate_profile"

# XLA flags that let the overlapped exchange actually run concurrently on
# GPU: collectives issued on their own async stream and the latency-hiding
# scheduler free to move them off the critical path (the standard
# set_platform recipe). GPU-ONLY: CPU/TPU jaxlib builds abort the process on
# unknown --xla_gpu_* flags in XLA_FLAGS, so these must never be appended
# unless a GPU platform is actually present.
_XLA_OVERLAP_FLAGS = (
    "--xla_gpu_enable_async_collectives=true",
    "--xla_gpu_enable_latency_hiding_scheduler=true",
    "--xla_gpu_enable_highest_priority_async_stream=true",
)


def xla_overlap_flags(platform: str | None = None) -> list[str]:
    """The async-collective XLA flags appropriate for ``platform``.

    ``None`` autodetects: 'gpu' only when a CUDA plugin is importable (the
    cheap check that cannot itself initialize a backend). Everything except
    'gpu' gets ``[]`` -- on this repo's CPU CI the flags would be a fatal
    ``Unknown flags in XLA_FLAGS`` abort, and on TPU the latency-hiding
    scheduler is already the default.
    """
    if platform is None:
        def _importable(mod: str) -> bool:
            try:
                # find_spec raises (not returns None) when the parent
                # package of a dotted name is itself missing.
                return importlib.util.find_spec(mod) is not None
            except ModuleNotFoundError:
                return False

        platform = "gpu" if any(
            _importable(mod)
            for mod in ("jax_cuda12_plugin", "jax_plugins.xla_cuda12")
        ) else "cpu"
    return list(_XLA_OVERLAP_FLAGS) if platform == "gpu" else []


def enable_overlap_flags(platform: str | None = None) -> bool:
    """Append the overlap flags to ``XLA_FLAGS`` (before backend init).

    Must run before the first jax device/backend call of the process --
    XLA parses the env var once at backend initialization. Returns whether
    anything was enabled (False on non-GPU platforms).
    """
    flags = xla_overlap_flags(platform)
    if not flags:
        return False
    current = os.environ.get("XLA_FLAGS", "")
    missing = [f for f in flags if f not in current]
    if missing:
        os.environ["XLA_FLAGS"] = " ".join([current, *missing]).strip()
    return True


class StopFlag:
    """SIGTERM/SIGINT -> "checkpoint at the next window boundary" flag.

    The handler only flips a bool (async-signal-safe); the windowed run loop
    polls it via ``stop_requested`` and performs the graceful stop -- drain
    the in-flight window, write the final checkpoint, raise ``Preempted`` --
    at the next window boundary, where the ring phase makes a bitwise resume
    possible.
    """

    def __init__(self):
        self.signum: int | None = None

    def __call__(self) -> bool:
        return self.signum is not None

    @property
    def name(self) -> str:
        return signal.Signals(self.signum).name if self.signum else "stop"

    def install(self) -> "StopFlag":
        def handler(signum, frame):
            del frame
            first = self.signum is None
            self.signum = signum
            if first:
                print(f"\n  caught {signal.Signals(signum).name}: finishing "
                      f"the current window, then checkpointing and exiting "
                      f"(repeat to kill immediately)", flush=True)
            else:
                signal.signal(signum, signal.SIG_DFL)
                signal.raise_signal(signum)

        signal.signal(signal.SIGTERM, handler)
        signal.signal(signal.SIGINT, handler)
        return self


def print_wire_volume(net, spec, cfg: EngineConfig, n_groups: int, gsz: int):
    """Dense-vs-routed wire bytes per window (static accounting).

    Pure shape/adjacency arithmetic (repro.core.exchange.wire_report) for an
    ``n_groups x gsz`` structure-aware mesh -- printable on a single host,
    no devices required; the distributed engines report the same numbers on
    ``Engine.wire_bytes``.
    """
    if (net.k_inter == 0 or n_groups < 2
            or net.n_areas % n_groups != 0 or net.n_pad % gsz != 0):
        # A single group has no inter-group traffic to route, and shapes
        # that don't shard would make the modelled bytes meaningless.
        print(f"\n-- wire volume: n/a (A={net.n_areas}, n_pad={net.n_pad} "
              f"on {n_groups} groups x {gsz})")
        return
    rep = exchange_lib.wire_report(
        net, area_adjacency(net, spec), backend=cfg.backend,
        n_groups=n_groups, gsz=gsz,
        headroom=cfg.s_max_headroom, floor=cfg.s_max_floor)
    dense, routed = rep["dense"], rep["routed"]
    print(f"\n-- wire volume (bytes/window, mesh-total, modelled for "
          f"{n_groups} groups x {gsz} subgroup, backend={cfg.backend}) --")
    print(f"{'exchange':10s} {'local':>12s} {'global':>12s} {'total':>12s}"
          f" {'rounds':>8s}")
    print(f"{'dense':10s} {dense['local_bytes']:12,d} "
          f"{dense['global_bytes']:12,d} {dense['total_bytes']:12,d} "
          f"{max(n_groups - 1, 0):8d}")
    print(f"{'routed':10s} {routed['local_bytes']:12,d} "
          f"{routed['global_bytes']:12,d} {routed['total_bytes']:12,d} "
          f"{routed['rounds']:8d}")
    # The adaptive two-phase model next to the static worst case: phase-1
    # count bytes + expectation-sized payload (live runs report measured
    # bytes from SimState.shipped_bytes).
    print(f"{'exchange':10s} {'counts':>12s} {'payload(exp)':>12s} "
          f"{'worst':>12s} {'saved':>12s}  (adaptive two-phase)")
    for name, entry in (("dense", dense), ("routed", routed)):
        ad = entry["adaptive"]
        if not ad["applies"]:
            print(f"{name:10s} {'n/a (bit-packed wire)':>12s}")
            continue
        print(f"{name:10s} {ad['counts_bytes']:12,d} "
              f"{ad['payload_bytes_expected']:12,d} "
              f"{ad['payload_bytes_worst']:12,d} {ad['saved_bytes']:12,d}")
    if net.tgt_inter is not None or net.tgt_inter_in is not None:
        sub = gsz if getattr(cfg, "subgroup_inter_tables", True) else 1
        tbl = exchange_lib.priced_inter_table_report(
            net, n_groups=n_groups, gsz=gsz,
            headroom=cfg.s_max_headroom, floor=cfg.s_max_floor,
            subgroup=sub)
        tb = tbl["table_bytes"]
        print(f"-- inter receive tables, per device: replicated "
              f"{tb['replicated']:,} B (K={tbl['k_out_replicated']}) vs "
              f"sharded {tb['sharded']:,} B (K={tbl['k_in_sharded']}, "
              f"{tbl['n_shards']} shards, {tb['reduction']:.1f}x)")


def _pick_mesh(n_dev: int, n_areas: int, n_pad: int):
    """A (data, model) mesh shape for the structure-aware placement.

    Prefers the largest area-parallel tier (groups) whose shard constraints
    hold: areas divide the groups, the padded area size divides the
    subgroup. Returns None if nothing fits.
    """
    for gsz in range(1, n_dev + 1):
        if n_dev % gsz:
            continue
        groups = n_dev // gsz
        if n_areas % groups == 0 and n_pad % gsz == 0:
            return groups, gsz
    return None


def _run_resilient(args, eng, net, mesh, exchange, n_windows):
    """The checkpointed/fault-injected leg of a run (schedule.run_windows).

    Resumes from ``--checkpoint-dir`` when asked (elastically resharding if
    the group count changed since the checkpoint was taken), wires the fault
    injector into both the run loop and the checkpoint writer, and converts
    simulated preemption into a clean exit with a resume hint. Returns
    ``(state, wall_s, windows_run)`` for the shared reporting path.
    """
    n_groups = int(mesh.shape["data"]) if mesh is not None else 1
    fault_cfg = faults_lib.parse_fault_specs(args.inject_fault,
                                             seed=args.seed)
    injector = None
    if fault_cfg.any_enabled:
        injector = faults_lib.FaultInjector(
            fault_cfg, n_devices=jax.device_count(),
            delay_ratio=eng.delay_ratio)
        if fault_cfg.jitter_enabled:
            print(f"  fault injection: per-device jitter mu="
                  f"{fault_cfg.jitter_mu_ms} ms sigma="
                  f"{fault_cfg.jitter_sigma_ms} ms/cycle -> predicted "
                  f"straggler overhead "
                  f"{injector.predicted_jitter_s() * 1e3:.2f} ms/window "
                  f"(order-statistics sync model)")
        if fault_cfg.comm_enabled:
            print(f"  fault injection: exchange straggler mu="
                  f"{fault_cfg.comm_mu_ms} ms sigma="
                  f"{fault_cfg.comm_sigma_ms} ms/window -> predicted wall "
                  f"sequential {injector.predicted_sequential_s() * 1e3:.2f}"
                  f" (sum) vs overlapped "
                  f"{injector.predicted_overlap_s() * 1e3:.2f} ms/window "
                  f"(Clark E[max])")
    start_w = 0
    if args.resume:
        st, info = schedule_lib.restore_sim(
            args.checkpoint_dir, eng, net, exchange=exchange,
            n_groups=n_groups)
        start_w = int(info["step"])
        resh = info["reshard"]
        if resh is not None:
            print(f"  resumed window {start_w} from {args.checkpoint_dir}: "
                  f"elastic reshard {resh['old_n_groups']} -> "
                  f"{resh['new_n_groups']} groups "
                  f"({resh['moved_areas']} areas re-homed)")
        else:
            print(f"  resumed window {start_w} from {args.checkpoint_dir} "
                  f"on {n_groups} group(s)")
    else:
        st = eng.init()
    ckpt = None
    if args.checkpoint_dir:
        ckpt = schedule_lib.SimCheckpointer(
            args.checkpoint_dir, eng, net, every=args.checkpoint_every,
            keep=args.checkpoint_keep, exchange=exchange,
            n_groups=n_groups, injector=injector)
    remaining = n_windows - start_w
    if remaining <= 0:
        raise SystemExit(
            f"checkpoint already covers {start_w} windows >= the requested "
            f"{n_windows}; increase --t-ms or start a fresh run")
    # A throwaway compile window would advance the trajectory, so the
    # resilient leg pays compilation inside its first timed window.
    stop = StopFlag().install()
    try:
        res = schedule_lib.run_windows(
            eng, st, remaining, checkpointer=ckpt, faults=injector,
            stop_requested=stop)
    except faults_lib.Preempted as exc:
        leg = exc.result.windows_done
        why = f"caught {stop.name}" if stop() else "simulated preemption"
        hint = (f"checkpoint written to {exc.checkpoint_path} -- resume "
                f"with --resume --checkpoint-dir {exc.checkpoint_path}"
                if exc.checkpoint_path
                else "no --checkpoint-dir was given, so nothing was saved")
        print(f"  PREEMPTED ({why}) after window {exc.window} "
              f"({leg} this leg); {hint}")
        raise SystemExit(0)
    if res.overlapped:
        print(f"  overlapped pipeline: {res.drains} in-flight drain(s) at "
              f"checkpoint/end boundaries")
    if ckpt is not None:
        ckpt.close()
        if ckpt.retry_count:
            print(f"  checkpoint writer retried {ckpt.retry_count} "
                  f"transient write failure(s)")
        if ckpt.saved_windows:
            print(f"  checkpoints at windows {ckpt.saved_windows} "
                  f"(every {args.checkpoint_every}, "
                  f"keep {args.checkpoint_keep})")
    if res.injected_sleep_s:
        print(f"  injected jitter: {res.injected_sleep_s:.3f} s total, "
              f"measured {res.injected_sleep_s / res.windows_done * 1e3:.2f} "
              f"ms/window over {res.windows_done} windows")
    return res.state, float(res.window_times_s.sum()), res.windows_done


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="mam_benchmark",
                    choices=["mam", "mam_benchmark"])
    ap.add_argument("--scale", type=float, default=0.002)
    ap.add_argument("--areas", type=int, default=8,
                    help="areas (mam_benchmark only)")
    ap.add_argument("--n-per-area", type=int, default=256)
    ap.add_argument("--k", type=int, default=32)
    ap.add_argument("--t-ms", type=float, default=500.0)
    ap.add_argument("--schedule", default="structure_aware",
                    choices=["conventional", "structure_aware"])
    ap.add_argument("--neuron", default=None,
                    choices=[None, "lif", "ignore_and_fire"])
    ap.add_argument("--backend", default="",
                    choices=["", "onehot", "scatter", "pallas", "event"],
                    help="delivery backend (repro.core.delivery); "
                         "default scatter")
    ap.add_argument("--exchange", default="dense",
                    choices=["dense", "routed"],
                    help="distributed global pathway (repro.core.exchange): "
                         "mesh-wide collectives vs connectivity-routed "
                         "packet rounds (structure-aware schedule only; "
                         "ignored on a single device)")
    ap.add_argument("--replicated-inter-tables", action="store_true",
                    help="keep the legacy replicated inter receive tables "
                         "on every device instead of the sharded inbound "
                         "slices (the bit-identity baseline of the "
                         "sharded-table refactor; distributed event/routed "
                         "paths only)")
    ap.add_argument("--no-subgroup-inter-tables", action="store_true",
                    help="keep the per-group inbound slices (and the "
                         "lane-replicated outgoing intra tables) instead of "
                         "the subgroup-sliced [S, gsz, rows, K_in] / "
                         "[gsz, A, n_pad, K] layouts (the bit-identity "
                         "baseline of the memory-diet PR; structure-aware "
                         "distributed paths only)")
    ap.add_argument("--sharded-build", action="store_true",
                    help="host-free construction "
                         "(EngineConfig.sharded_build): each device's "
                         "inbound inter slices and lane-cut intra tables "
                         "are generated directly from the seeded "
                         "counter-based connectivity rules "
                         "(dist_engine.build_network_sharded) -- no process "
                         "materialises the global synapse tensors. "
                         "Bitwise-identical trajectories to the host build; "
                         "structure-aware event-backend legs on a "
                         "multi-device mesh only")
    ap.add_argument("--seed", type=int, default=12,
                    help="paper seeds: 12, 654, 91856")
    ap.add_argument("--adaptive", action="store_true",
                    help="adaptive two-phase exchange "
                         "(EngineConfig.adaptive_exchange): counts first, "
                         "then bucket-sized payloads; SimState.overflow is "
                         "provably 0 and asserted after every run")
    ap.add_argument("--overlap", action="store_true",
                    help="double-buffered window pipeline "
                         "(EngineConfig.overlap_exchange): window w's "
                         "payload exchange overlaps window w+1's compute -- "
                         "bitwise-identical trajectory, structure-aware "
                         "schedule only; on GPU also enables XLA's "
                         "async-collective + latency-hiding-scheduler flags")
    ap.add_argument("--compare", action="store_true",
                    help="run both schedules, assert identical spikes")
    ap.add_argument("--compare-adaptive", action="store_true",
                    help="run every selected schedule with BOTH the static "
                         "and the adaptive exchange, assert bit-identical "
                         "spike counts and zero adaptive overflow")
    ap.add_argument("--compare-overlap", action="store_true",
                    help="run every structure-aware leg BOTH sequential and "
                         "overlapped, assert bit-identical spike counts; "
                         "with a jitter-only --inject-fault spec the legs "
                         "run through the fault harness and the pipelined "
                         "injected wall must beat the sequential one")
    ap.add_argument("--profile", action="store_true",
                    help=f"run each leg's timed windows through the "
                         f"windowed run loop under jax.profiler.trace into "
                         f"{PROFILE_DIR}/ (open it in TensorBoard or "
                         f"Perfetto: device ops group by the window's named "
                         f"scopes {', '.join(schedule_lib.SCOPES)}, host "
                         f"work by the repro.window.* spans), and print "
                         f"the dense-vs-routed wire volume before the run")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="window-boundary SimState checkpoints through "
                         "checkpoint.AsyncWriter land here; enables the "
                         "resilient windowed run loop")
    ap.add_argument("--checkpoint-every", type=int, default=50,
                    help="checkpoint cadence in completed windows "
                         "(default 50)")
    ap.add_argument("--checkpoint-keep", type=int, default=3,
                    help="retain this many newest checkpoints (default 3)")
    ap.add_argument("--resume", action="store_true",
                    help="restore the latest checkpoint from "
                         "--checkpoint-dir and continue -- bitwise-identical "
                         "to the uninterrupted run, elastically resharding "
                         "when the group count changed")
    ap.add_argument("--inject-fault", action="append", default=[],
                    metavar="SPEC",
                    help="deterministic fault injection (repeatable): "
                         "'jitter:mu_ms=1.6,sigma_ms=0.3[,rho=R][,devices=N]"
                         "[,comm_mu_ms=M][,comm_sigma_ms=S]' per-device "
                         "compute jitter plus a per-window exchange "
                         "straggler, 'ckpt-io:fails=K' transient "
                         "checkpoint-write failures, 'preempt:window=W' "
                         "SIGTERM-style stop after W completed windows")
    ap.add_argument("--spikes-out", default=None,
                    help="write the final per-neuron spike_count to this "
                         ".npz (CI resume-equality checks)")
    args = ap.parse_args()
    enable_compile_cache()

    # --compare-overlap + a jitter-only fault spec is the one sanctioned
    # fault/compare combination: every leg runs the fault harness with the
    # same deterministic draws, so the sequential-vs-pipelined injected
    # walls are directly comparable (the paper's max-vs-sum claim).
    inject_compare = bool(args.inject_fault and args.compare_overlap)
    resilient = bool(args.checkpoint_dir or args.resume
                     or (args.inject_fault and not inject_compare))
    if resilient and (args.compare or args.compare_adaptive
                      or args.compare_overlap):
        raise SystemExit(
            "--checkpoint-dir/--resume/--inject-fault run one trajectory; "
            "they cannot be combined with --compare/--compare-adaptive/"
            "--compare-overlap (exception: --compare-overlap with a "
            "jitter-only --inject-fault spec)")
    if args.resume and not args.checkpoint_dir:
        raise SystemExit("--resume needs --checkpoint-dir")
    if args.profile and (resilient or inject_compare):
        raise SystemExit(
            "--profile traces the plain windowed run; it cannot be combined "
            "with --checkpoint-dir/--resume/--inject-fault")
    compare_fault_cfg = None
    if inject_compare:
        if args.compare or args.compare_adaptive:
            raise SystemExit(
                "--inject-fault with --compare-overlap cannot also run "
                "--compare/--compare-adaptive legs")
        compare_fault_cfg = faults_lib.parse_fault_specs(
            args.inject_fault, seed=args.seed)
        if (compare_fault_cfg.preempt_after_window > 0
                or compare_fault_cfg.ckpt_write_failures > 0):
            raise SystemExit(
                "--compare-overlap only accepts jitter specs in "
                "--inject-fault; preempt/ckpt-io faults run one trajectory")
    wants_overlap = args.overlap or args.compare_overlap
    if wants_overlap and args.schedule == "conventional" and not args.compare:
        raise SystemExit(
            "--overlap/--compare-overlap need the structure-aware schedule "
            "(the conventional schedule has no window-end exchange to hide)")
    if wants_overlap and enable_overlap_flags():
        print("XLA async-collective/latency-hiding flags enabled (gpu)")

    if args.model == "mam":
        spec = mam_spec(scale=args.scale)
        neuron = args.neuron or "lif"
    else:
        spec = mam_benchmark_spec(
            n_areas=args.areas, n_per_area=args.n_per_area,
            k_intra=args.k // 2, k_inter=args.k // 2)
        neuron = args.neuron or "ignore_and_fire"
    backend = args.backend or "scatter"
    needs_outgoing = backend == "event" or args.exchange == "routed"
    n_dev = jax.device_count()
    print(f"{args.model}: {spec.n_total:,} neurons / {spec.n_areas} areas, "
          f"K={spec.k_total}, D={spec.delay_ratio}, neuron={neuron}, "
          f"backend={backend}, exchange={args.exchange}, seed={args.seed}, "
          f"devices={n_dev}")

    n_pad_spec = spec.padded_area_size(1)
    if args.sharded_build:
        if backend != "event":
            raise SystemExit(
                "--sharded-build generates the event path's tables; run "
                "with --backend event")
        if args.replicated_inter_tables:
            raise SystemExit(
                "--sharded-build emits per-shard inbound slices; it cannot "
                "combine with --replicated-inter-tables")
        if n_dev <= 1:
            raise SystemExit(
                "--sharded-build needs a multi-device mesh (the single-host "
                "engine holds the whole network anyway)")
        if args.schedule == "conventional" and not args.compare:
            raise SystemExit(
                "--sharded-build targets the structure-aware placement; "
                "the conventional schedule slices a host-built network")

    # The host-built global network: skipped entirely when every leg builds
    # sharded (the whole point -- its host RSS is the construction wall).
    # The conventional --compare legs and --profile's wire table still
    # need it.
    runs_conventional = args.compare or args.schedule == "conventional"
    needs_host_net = ((not args.sharded_build) or runs_conventional
                      or args.profile)
    net = (build_network(spec, seed=args.seed, outgoing=needs_outgoing)
           if needs_host_net else None)
    mesh = None
    if n_dev > 1:
        shape = _pick_mesh(n_dev, spec.n_areas, n_pad_spec)
        if shape is None:
            raise SystemExit(
                f"no (data, model) mesh over {n_dev} devices fits "
                f"A={spec.n_areas}, n_pad={n_pad_spec}")
        if runs_conventional and n_pad_spec % n_dev != 0:
            # The round-robin placement slices every area over all devices.
            raise SystemExit(
                f"the conventional schedule needs n_pad={n_pad_spec} "
                f"divisible by {n_dev} devices (pick --n-per-area "
                "accordingly, or run --schedule structure_aware)")
        mesh = jax.make_mesh(shape, ("data", "model"))
        print(f"mesh: {shape[0]} area groups x {shape[1]} subgroup devices")

    base_cfg = EngineConfig(
        neuron_model=neuron, schedule=args.schedule,
        delivery_backend=backend, seed=42)
    if args.profile:
        n_groups, gsz = (
            (mesh.shape["data"], mesh.shape["model"]) if mesh is not None
            else _pick_mesh(8, net.n_areas, net.n_pad) or (1, 8))
        print_wire_volume(net, spec, base_cfg, n_groups, gsz)

    schedules = ([args.schedule] if not args.compare
                 else ["conventional", "structure_aware"])
    adaptives = ([False, True] if args.compare_adaptive
                 else [args.adaptive])
    spikes = {}
    injected = {}
    for sched in schedules:
        for adaptive in adaptives:
          overlaps = ([False, True]
                      if args.compare_overlap and sched == "structure_aware"
                      else [args.overlap and sched == "structure_aware"])
          for overlap_on in overlaps:
            # The routed exchange routes the structure-aware window's lumped
            # global pathway; the conventional schedule always runs dense.
            exchange = (args.exchange if sched == "structure_aware"
                        else "dense")
            sharded_leg = (args.sharded_build and mesh is not None
                           and sched == "structure_aware")
            cfg = EngineConfig(
                neuron_model=neuron, schedule=sched,
                delivery_backend=backend,
                exchange=exchange if mesh is not None else "", seed=42,
                shard_inter_tables=not args.replicated_inter_tables,
                subgroup_inter_tables=not args.no_subgroup_inter_tables,
                adaptive_exchange=adaptive, overlap_exchange=overlap_on,
                sharded_build=sharded_leg)
            leg_net = net
            if mesh is not None:
                from repro.core.dist_engine import build_network_sharded

                if sharded_leg:
                    t0 = time.perf_counter()
                    leg_net = build_network_sharded(
                        spec, mesh, cfg, seed=args.seed)
                    jax.block_until_ready(leg_net.tgt_intra)
                    print(f"  sharded build: tables generated host-free in "
                          f"{time.perf_counter() - t0:.2f} s "
                          f"(no global tensors materialised)")
                eng = make_simulation(spec, cfg, net=leg_net, mesh=mesh)
            else:
                eng = make_simulation(spec, cfg, net=net)
            n_windows = spec.steps_for(args.t_ms) // spec.delay_ratio
            if resilient:
                st, wall, windows_run = _run_resilient(
                    args, eng, leg_net, mesh, exchange, n_windows)
            elif inject_compare:
                # Same deterministic draws for every leg (injector state is
                # keyed by (seed, window)), so the injected walls realize
                # the exact sum-vs-max quantities the sync model prices.
                injector = faults_lib.FaultInjector(
                    compare_fault_cfg, n_devices=n_dev,
                    delay_ratio=eng.delay_ratio)
                res = schedule_lib.run_windows(
                    eng, eng.init(), n_windows, faults=injector)
                st = res.state
                wall = float(res.window_times_s.sum())
                windows_run = res.windows_done
                injected[(sched, adaptive, overlap_on)] = res.injected_sleep_s
            elif args.profile:
                # The real run, traced: one dispatch per window, so the
                # trace shows the window program and the host work between.
                st = eng.init()
                st, _ = eng.window(st)  # compile
                jax.block_until_ready(st.ring)
                t0 = time.perf_counter()
                with jax.profiler.trace(PROFILE_DIR):
                    res = schedule_lib.run_windows(eng, st, n_windows - 1)
                wall = time.perf_counter() - t0
                st = res.state
                windows_run = res.windows_done
                print(f"  profiler trace -> {os.path.abspath(PROFILE_DIR)}")
            else:
                st = eng.init()
                st, _ = eng.window(st)  # compile
                jax.block_until_ready(st.ring)
                t0 = time.perf_counter()
                st, per_win = eng.run(st, n_windows - 1)
                jax.block_until_ready(st.ring)
                wall = time.perf_counter() - t0
                windows_run = n_windows - 1
            t_s = float(st.t) * spec.dt_ms / 1000.0
            rate = float(st.spike_count.sum()) / (spec.n_total * t_s)
            rtf = wall / (
                max(windows_run, 1) * spec.delay_ratio * spec.dt_ms / 1000)
            overflow = int(st.overflow)
            wire = eng.wire_bytes or {}
            wire_s = (f", {wire['total_bytes']:,} wire B/window (static)"
                      if wire.get("total_bytes") else "")
            measured = float(st.shipped_bytes) / n_windows
            meas_s = (f", {measured:,.0f} measured B/window"
                      if measured else "")
            mode = ("adaptive" if adaptive else "static") + \
                   ("+overlap" if overlap_on else "")
            print(f"  {sched:16s} "
                  f"({exchange if mesh is not None else 'local'}/{mode}):"
                  f" {wall:6.2f} s wall, RTF {rtf:8.1f}, "
                  f"mean rate {rate:5.2f} Hz, "
                  f"{int(st.spike_count.sum()):,} spikes{wire_s}{meas_s}"
                  + (f", OVERFLOW {overflow} (raise s_max!)"
                     if overflow else ""))
            if adaptive and overflow:
                raise SystemExit(
                    "adaptive exchange reported nonzero overflow -- the "
                    "two-phase sizing is broken (this must be impossible)")
            spikes[(sched, adaptive, overlap_on)] = np.asarray(st.spike_count)
            if args.spikes_out:
                np.savez(args.spikes_out,
                         spike_count=np.asarray(st.spike_count),
                         t=int(st.t))
                print(f"  spike counts -> {args.spikes_out}")

    if args.compare:
        for adaptive in adaptives:
            ref = spikes[("conventional", adaptive, False)]
            for (sched, ad, ovl), spk in spikes.items():
                if sched == "conventional" or ad != adaptive:
                    continue
                same = np.array_equal(ref, spk)
                mode = ("adaptive" if ad else "static") + \
                       ("+overlap" if ovl else "")
                print(f"\nschedules produce identical spike counts "
                      f"({mode}): {same}")
                if not same:
                    raise SystemExit(1)
    if args.compare_adaptive:
        for sched in schedules:
            for ovl in sorted({o for (s, _, o) in spikes if s == sched}):
                same = np.array_equal(spikes[(sched, False, ovl)],
                                      spikes[(sched, True, ovl)])
                print(f"adaptive == static spike counts "
                      f"({sched}{'/overlap' if ovl else ''}): {same}")
                if not same:
                    raise SystemExit(1)
    if args.compare_overlap:
        for (sched, adaptive, ovl) in sorted(spikes):
            if not ovl:
                continue
            same = np.array_equal(spikes[(sched, adaptive, False)],
                                  spikes[(sched, adaptive, True)])
            mode = "adaptive" if adaptive else "static"
            print(f"overlapped == sequential spike counts "
                  f"({sched}/{mode}): {same}")
            if not same:
                raise SystemExit(1)
        if inject_compare and compare_fault_cfg.comm_enabled:
            for (sched, adaptive, ovl), pipe_wall in sorted(
                    injected.items()):
                if not ovl:
                    continue
                seq_wall = injected[(sched, adaptive, False)]
                mode = "adaptive" if adaptive else "static"
                print(f"injected wall ({sched}/{mode}): sequential "
                      f"{seq_wall:.3f} s (sum) vs pipelined "
                      f"{pipe_wall:.3f} s (max) -- "
                      f"{(1 - pipe_wall / seq_wall) * 100:.1f}% hidden")
                if not pipe_wall < seq_wall:
                    raise SystemExit(
                        "pipelined injected wall failed to beat the "
                        "sequential wall under jitter -- the overlap is "
                        "not hiding the exchange")


if __name__ == "__main__":
    main()
