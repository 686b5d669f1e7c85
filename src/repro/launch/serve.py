"""Simulation-as-a-service: a batched multi-tenant serving layer.

JetStream-style serving on top of the unified engine API
(:func:`repro.core.make_simulation`): many independent tenants submit
*trials* -- ``(seed, stimulus scale, duration)`` -- against one shared
network spec, and the server packs them into batches that run as a single
engine dispatch per window.

**Folded batching.** A batch of ``B`` trials runs as ONE block-diagonal
super-network: the connectivity is tiled ``B`` times along the area axis
(:func:`repro.core.connectivity.tile_network` -- no synapse crosses a copy
boundary), each copy is fed the single-trial gid table
(:func:`~repro.core.connectivity.tile_gids`) and its own per-trial
``seed``/``stim`` drive leaves (:class:`~repro.core.schedule.SimState`).
Each block then reproduces the corresponding single-trial run *bitwise*
(1/256-grid weights make ring accumulation associative-exact, and the
per-copy scatter order is the single-trial order), while the batch pays
the per-window dispatch and host-loop overhead once instead of ``B``
times. Unlike a ``vmap`` over trials -- which lowers the event path's
sorts and scatters to slow batched variants -- the folded network runs
the *single-trial* code shape. How much of the window that amortises is
host-dependent: on accelerators the fixed per-dispatch cost dominates
small windows; on a single-core CPU host per-neuron compute dominates
and the fold's warm-loop gain is small. The serving layer's headline
throughput win there is the startup warm instead -- every tenant
shares one compiled executable rather than paying engine build + jit
compile per trial (>=2x over per-trial cold clients is the benchmarked
floor; see ``benchmarks/bench_delivery.py::bench_serve``).

**Execution model.** At startup the server builds the folded engine and
compiles and warms its window executable with a filler batch. One *executor* thread owns all device
work (one host process drives one device queue; submitters are free to be
many): it groups queued requests by duration bucket (a power-of-two ladder
of window counts), assembles the per-copy drive leaves, and advances the
batch window by window through :func:`repro.core.schedule.run_windows`,
whose ``on_block`` hook is the per-request streaming cadence -- every
window, each trial's rows are sliced out of the ``[D, B*A, n_pad]`` spike
block and a request finalises the moment its *own* duration completes,
independent of the batch's longest trial. The window executable is
duration-independent, so every bucket shares one compiled artifact;
buckets exist to pack requests of similar length together (a short trial
never waits out a long batch-mate's tail).

**Draining.** ``SIGTERM`` (or :meth:`SimServer.shutdown`) flips the server
to draining: new submissions are rejected with :class:`ServerClosed`,
accepted requests are run to completion, and on a non-draining shutdown
the unserved requests are journaled through :mod:`repro.checkpoint.manager`
(atomic ``step_<N>/`` directory) so a restarted server can resubmit them.

CLI::

    PYTHONPATH=src python -m repro.launch.serve --trials 16 --batch 8
    PYTHONPATH=src python -m repro.launch.serve --selftest   # CI smoke
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import signal
import threading
import time
from typing import Callable

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.areas import MultiAreaSpec, tile_spec
from repro.core import connectivity as connectivity_lib
from repro.core.engine import EngineConfig
from repro.core.factory import make_simulation
from repro.core import schedule as schedule_lib

__all__ = [
    "TrialRequest",
    "TrialResult",
    "TrialHandle",
    "ServerClosed",
    "SimServer",
    "serve_simulation",
]


class ServerClosed(RuntimeError):
    """Raised by ``submit`` once the server is draining or stopped."""


@dataclasses.dataclass(frozen=True)
class TrialRequest:
    """One tenant's trial: an independent simulation of the shared spec.

    ``seed`` keys the counter-based external drive (the trial's only
    source of randomness -- trajectories are a pure function of it);
    ``stim`` scales the drive rate (1.0 = the spec's calibrated ground
    state); ``windows`` is the duration in D-cycle windows.
    """

    seed: int
    stim: float = 1.0
    windows: int = 1

    def __post_init__(self) -> None:
        if self.windows < 1:
            raise ValueError(f"windows must be >= 1, got {self.windows}")


@dataclasses.dataclass
class TrialResult:
    request: TrialRequest
    # [windows * D, A, n_pad] bool -- the trial's full spike train.
    spikes: np.ndarray
    # The batch's overflow counter after this trial's run. 0 is the event
    # path's exactness condition; nonzero means packet bounds clipped.
    overflow: int
    # Seconds from submit to result (queue wait + compute).
    latency_s: float


class TrialHandle:
    """Future for a submitted trial; fulfilled by the executor thread."""

    def __init__(self, request: TrialRequest,
                 on_block: Callable[[int, np.ndarray], None] | None = None):
        self.request = request
        self._on_block = on_block
        self._event = threading.Event()
        self._result: TrialResult | None = None
        self._error: BaseException | None = None
        self._t_submit = time.perf_counter()

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: float | None = None) -> TrialResult:
        if not self._event.wait(timeout):
            raise TimeoutError("trial not finished")
        if self._error is not None:
            raise self._error
        assert self._result is not None
        return self._result

    # -- executor side ---------------------------------------------------
    def _stream(self, w: int, rows: np.ndarray) -> None:
        if self._on_block is not None:
            self._on_block(w, rows)

    def _fulfil(self, spikes: np.ndarray, overflow: int) -> None:
        self._result = TrialResult(
            request=self.request, spikes=spikes, overflow=overflow,
            latency_s=time.perf_counter() - self._t_submit)
        self._event.set()

    def _fail(self, err: BaseException) -> None:
        self._error = err
        self._event.set()


def _bucket_ladder(max_windows: int) -> tuple[int, ...]:
    """Power-of-two duration buckets up to (and including) max_windows."""
    ladder = []
    w = 1
    while w < max_windows:
        ladder.append(w)
        w *= 2
    ladder.append(max_windows)
    return tuple(ladder)


class SimServer:
    """Batched multi-tenant trial server over one folded engine.

    ``max_batch`` trials run per dispatch as a ``max_batch``-copy
    block-diagonal super-network (see the module docstring); unfilled
    slots are padded with filler trials whose results are dropped.
    ``max_batch=1`` is the sequential-loop baseline the benchmark
    compares against -- same machinery, no folding.
    """

    def __init__(
        self,
        spec: MultiAreaSpec,
        config: EngineConfig = EngineConfig(delivery_backend="event"),
        *,
        max_batch: int = 16,
        max_windows: int = 32,
        build_seed: int = 12,
        checkpoint_dir: str | None = None,
    ):
        if max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        if config.neuron_model != "lif":
            raise ValueError(
                "serving needs neuron_model='lif': trials are distinguished "
                "by their drive seed, and ignore_and_fire has no seed or "
                "input dependence (every trial would be identical)")
        if config.superstep_kernel:
            raise ValueError(
                "serving needs per-trial seed leaves, which the fused "
                "superstep kernel does not take (it bakes cfg.seed in)")
        self.spec = spec
        self.config = config
        self.max_batch = max_batch
        self.buckets = _bucket_ladder(max_windows)
        self.checkpoint_dir = checkpoint_dir

        # ---- build the folded engine (B network copies, one executable).
        net = connectivity_lib.build_network(
            spec, seed=build_seed, outgoing=config.backend == "event")
        self._A, self._n_pad = net.alive.shape
        B = max_batch
        self._spec_b = tile_spec(spec, B)
        net_b = connectivity_lib.tile_network(net, B)
        gids_b = connectivity_lib.tile_gids(self._A, self._n_pad, B)
        # The event path's whole-network packet bound carries a constant
        # `+ 4*floor` burst term that does NOT grow with the fold: a B-copy
        # batch would run a strictly tighter per-copy bound than its B
        # sequential references and clip first -- and a clipped global
        # packet mixes copies (cross-trial interference). s_max_burst=B
        # widens exactly that term, keeping the folded global bound >= the
        # sum of the sequential ones while leaving the per-area bound (and
        # so the per-area scatter width, the event path's cost driver)
        # untouched; widths beyond the realised spike count are inert
        # (invalid-id padding), so this cannot change an unclipped
        # trajectory.
        cfg_b = dataclasses.replace(
            config, s_max_burst=config.s_max_burst * B)
        self.engine = make_simulation(
            self._spec_b, cfg_b, net=net_b, gids=gids_b)
        self.delay_ratio = self.engine.delay_ratio

        # ---- request plumbing.
        self._lock = threading.Condition()
        self._queue: list[TrialHandle] = []
        self._closed = False
        self._drain = True
        self._stopped = threading.Event()
        self._worker: threading.Thread | None = None

        # ---- SLO bookkeeping.
        self._latencies: list[float] = []
        self._trials_done = 0
        self._t_started: float | None = None
        self._busy_s = 0.0

    # ------------------------------------------------------------------
    # lifecycle

    def start(self) -> "SimServer":
        """Compile + warm the window executable, start the executor."""
        st = self._init_state(
            [TrialRequest(seed=int(self.config.seed))] )
        # One window executable serves every duration bucket (the windowed
        # executor streams blocks; a fixed-length scan would return only
        # spike counts). Compile it for the folded state shape with one
        # real dispatch so the first tenant never pays compile or
        # first-touch cost.
        out_st, _ = self.engine.window(st)
        import jax
        jax.block_until_ready(out_st.ring)
        self._t_started = time.perf_counter()
        self._worker = threading.Thread(
            target=self._run_loop, name="sim-serve-executor", daemon=True)
        self._worker.start()
        return self

    def __enter__(self) -> "SimServer":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.shutdown()

    def install_sigterm(self) -> None:
        """SIGTERM -> drain: reject new submissions, finish accepted ones."""
        signal.signal(signal.SIGTERM, lambda *_: self.close(drain=True))

    def close(self, *, drain: bool = True) -> None:
        """Stop accepting; signal the executor to drain (or abandon)."""
        with self._lock:
            self._closed = True
            self._drain = drain
            self._lock.notify_all()

    def shutdown(self, *, drain: bool = True, timeout: float | None = None
                 ) -> None:
        """Close, wait for the executor, journal anything unserved."""
        self.close(drain=drain)
        if self._worker is not None:
            self._worker.join(timeout)
        self._journal_unserved()

    # ------------------------------------------------------------------
    # tenant side

    def submit(self, request: TrialRequest,
               on_block: Callable[[int, np.ndarray], None] | None = None,
               ) -> TrialHandle:
        """Queue a trial; returns its handle (thread-safe).

        ``on_block(w, rows)`` streams the trial's own ``[D, A, n_pad]``
        spike rows after every window, from the executor thread.
        """
        if request.windows > self.buckets[-1]:
            raise ValueError(
                f"windows={request.windows} exceeds the server's "
                f"max_windows={self.buckets[-1]}")
        handle = TrialHandle(request, on_block)
        with self._lock:
            if self._closed:
                raise ServerClosed("server is draining; not accepting trials")
            self._queue.append(handle)
            self._lock.notify_all()
        return handle

    def stats(self) -> dict:
        """Serving SLOs so far: trials/s and p50/p99 time-to-result."""
        lat = np.asarray(self._latencies, dtype=np.float64)
        elapsed = (time.perf_counter() - self._t_started
                   if self._t_started else 0.0)
        return dict(
            trials=self._trials_done,
            max_batch=self.max_batch,
            elapsed_s=elapsed,
            busy_s=self._busy_s,
            trials_per_s=(self._trials_done / elapsed) if elapsed else 0.0,
            p50_ms=float(np.percentile(lat, 50) * 1e3) if lat.size else None,
            p99_ms=float(np.percentile(lat, 99) * 1e3) if lat.size else None,
        )

    # ------------------------------------------------------------------
    # executor side

    def _bucket_for(self, windows: int) -> int:
        for b in self.buckets:
            if windows <= b:
                return b
        return self.buckets[-1]

    def _init_state(self, requests: list[TrialRequest]):
        """The folded batch's initial SimState: per-copy seed/stim leaves."""
        import jax.numpy as jnp

        A, n_pad, B = self._A, self._n_pad, self.max_batch
        seeds = [int(r.seed) for r in requests]
        stims = [float(r.stim) for r in requests]
        # Filler copies run the engine-wide seed at unit stimulus; their
        # blocks are discarded (block-diagonality keeps them from touching
        # any tenant's copy).
        seeds += [int(self.config.seed)] * (B - len(seeds))
        stims += [1.0] * (B - len(stims))
        seed_leaf = jnp.broadcast_to(
            jnp.repeat(jnp.asarray(seeds, jnp.uint32), A)[:, None],
            (B * A, n_pad))
        stim_leaf = jnp.broadcast_to(
            jnp.repeat(jnp.asarray(stims, jnp.float32), A)[:, None],
            (B * A, n_pad))
        st = self.engine.init(seed=0, stim=1.0)
        return dataclasses.replace(st, seed=seed_leaf, stim=stim_leaf)

    def _take_batch(self) -> list[TrialHandle] | None:
        """Block for work; group up to max_batch same-bucket requests."""
        with self._lock:
            while not self._queue:
                if self._closed:
                    return None
                self._lock.wait(timeout=0.1)
            if self._closed and not self._drain:
                return None
            bucket = self._bucket_for(self._queue[0].request.windows)
            batch, rest = [], []
            for h in self._queue:
                if (len(batch) < self.max_batch
                        and self._bucket_for(h.request.windows) == bucket):
                    batch.append(h)
                else:
                    rest.append(h)
            self._queue = rest
            return batch

    def _run_batch(self, batch: list[TrialHandle]) -> None:
        import jax

        A, D = self._A, self.delay_ratio
        bucket = max(self._bucket_for(h.request.windows) for h in batch)
        st = self._init_state([h.request for h in batch])
        collected: list[list[np.ndarray]] = [[] for _ in batch]
        done = [False] * len(batch)

        def on_block(w: int, block) -> None:
            host = np.asarray(block)  # [D, B*A, n_pad] bool
            for i, h in enumerate(batch):
                if done[i]:
                    continue
                rows = host[:, i * A:(i + 1) * A, :]
                collected[i].append(rows)
                h._stream(w, rows)
                if len(collected[i]) >= h.request.windows:
                    done[i] = True
        t0 = time.perf_counter()
        res = schedule_lib.run_windows(
            self.engine, st, bucket, on_block=on_block)
        jax.block_until_ready(res.state.ring)
        self._busy_s += time.perf_counter() - t0
        overflow = int(jax.device_get(res.state.overflow))
        for i, h in enumerate(batch):
            spikes = np.concatenate(collected[i][:h.request.windows], axis=0)
            h._fulfil(spikes[:h.request.windows * D], overflow)
            self._latencies.append(time.perf_counter() - h._t_submit)
            self._trials_done += 1

    def _run_loop(self) -> None:
        try:
            while True:
                batch = self._take_batch()
                if batch is None:
                    break
                try:
                    self._run_batch(batch)
                except BaseException as e:  # noqa: BLE001 -- fail the batch
                    for h in batch:
                        h._fail(e)
        finally:
            self._stopped.set()

    def _journal_unserved(self) -> None:
        """Write unserved requests through the checkpoint manager.

        Only a non-draining shutdown leaves anything unserved; the journal
        (atomic ``step_<N>/`` rename, crash-safe) lets a restarted server
        resubmit exactly the trials that were accepted but never ran.
        """
        with self._lock:
            unserved = list(self._queue)
            self._queue = []
        for h in unserved:
            h._fail(ServerClosed("server stopped before this trial ran"))
        if not unserved or self.checkpoint_dir is None:
            return
        from repro.checkpoint import manager as ckpt

        reqs = [dataclasses.asdict(h.request) for h in unserved]
        ckpt.save(
            self.checkpoint_dir, step=int(time.time()),
            tree={"n_unserved": np.int64(len(reqs))},
            extra={"unserved": reqs})

    @staticmethod
    def restore_unserved(checkpoint_dir: str) -> list[TrialRequest]:
        """Read back a journal written by a non-draining shutdown."""
        from repro.checkpoint import manager as ckpt

        manifest, _ = ckpt.read_manifest(checkpoint_dir)
        extra = manifest.get("extra") or {}
        return [TrialRequest(**r) for r in extra.get("unserved", [])]


def serve_simulation(
    spec: MultiAreaSpec,
    config: EngineConfig = EngineConfig(delivery_backend="event"),
    **kw,
) -> SimServer:
    """Build and start a :class:`SimServer` (the module's entry point)."""
    return SimServer(spec, config, **kw).start()


# ----------------------------------------------------------------------
# CLI


def _laptop_spec(scale: float) -> MultiAreaSpec:
    from repro.core.areas import mam_spec

    return mam_spec(scale=scale)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--scale", type=float, default=0.002,
                    help="MAM downscale factor (laptop config)")
    ap.add_argument("--batch", type=int, default=8,
                    help="max trials folded per dispatch")
    ap.add_argument("--trials", type=int, default=16)
    ap.add_argument("--windows", type=int, default=8,
                    help="duration of each trial, in D-cycle windows")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--vth", type=float, default=15.0,
                    help="LIF threshold (mV); the selftest lowers it to 2.0 "
                         "so the short smoke trials actually spike")
    ap.add_argument("--selftest", action="store_true",
                    help="CI smoke: mixed batch, assert bitwise equality "
                         "to sequential references and nonzero trials/s")
    args = ap.parse_args(argv)
    enable_compile_cache()

    from repro.core.neuron import LIFParams

    spec = _laptop_spec(args.scale)
    vth = 2.0 if args.selftest else args.vth
    # The lowered selftest threshold drives bursty onset activity far above
    # the 2.5 Hz calibration the default packet bounds price; exactness
    # needs overflow == 0, so raise the floor to the per-area population
    # bound (n_pad is hard per cycle; the selftest asserts overflow == 0,
    # which also covers the whole-net packet's realised peak).
    floor = max(16, spec.padded_area_size(1)) if args.selftest else 16
    cfg = EngineConfig(delivery_backend="event",
                       lif=LIFParams(v_th_mv=vth),
                       s_max_floor=floor)
    rng = np.random.default_rng(0)
    requests = [
        TrialRequest(seed=int(rng.integers(1, 2**31)),
                     stim=float(rng.uniform(0.8, 1.2)),
                     windows=int(rng.integers(1, args.windows + 1))
                     if args.selftest else args.windows)
        for _ in range(args.trials)
    ]

    with SimServer(spec, cfg, max_batch=args.batch,
                   max_windows=args.windows,
                   checkpoint_dir=args.checkpoint_dir) as server:
        server.install_sigterm()
        handles = [server.submit(r) for r in requests]
        results = [h.result(timeout=600) for h in handles]
    stats = server.stats()
    print(json.dumps({k: v for k, v in stats.items()}, indent=2))

    if args.selftest:
        # Bitwise equality: every served trial == its sequential reference.
        eng = make_simulation(spec, cfg)
        for r in results:
            st = eng.init(seed=r.request.seed, stim=r.request.stim)
            blocks = []
            for _ in range(r.request.windows):
                st, blk = eng.window(st)
                blocks.append(np.asarray(blk))
            ref = np.concatenate(blocks, axis=0)
            assert r.spikes.shape == ref.shape, (r.spikes.shape, ref.shape)
            assert np.array_equal(r.spikes, ref), (
                f"trial seed={r.request.seed} diverged from its "
                "sequential reference")
            assert r.overflow == 0, "overflow must be 0 for exactness"
        assert stats["trials_per_s"] > 0, "no throughput recorded"
        print(f"selftest OK: {len(results)} trials bitwise-identical to "
              f"sequential references at "
              f"{stats['trials_per_s']:.2f} trials/s")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
