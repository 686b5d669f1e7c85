"""End-to-end behaviour tests for the paper's system.

The central invariant: the conventional and structure-aware schedules are
*exactly* equivalent -- bit-identical spike trains and ring buffers -- because
inter-area delays >= D cycles make the lumped exchange causal (paper §2.1),
and delivery weights live on an exact 1/256 grid.
"""

import numpy as np
import pytest

from repro.core.areas import MAM_AREA_NAMES, mam_benchmark_spec, mam_spec
from repro.core.connectivity import build_network
from repro.core.engine import EngineConfig
from repro.core.factory import make_simulation


@pytest.fixture(scope="module")
def small_spec():
    return mam_benchmark_spec(n_areas=4, n_per_area=48, k_intra=8, k_inter=8)


@pytest.fixture(scope="module")
def small_net(small_spec):
    return build_network(small_spec, seed=12)


@pytest.mark.parametrize("neuron_model", ["ignore_and_fire", "lif"])
def test_schedule_equivalence_bit_exact(small_spec, small_net, neuron_model):
    """Paper §2.1: the structure-aware schedule changes *when* spikes travel,
    never *what* arrives. 40 windows, bitwise."""
    conv = make_simulation(small_spec, EngineConfig(neuron_model=neuron_model,
                                    schedule="conventional"), net=small_net)
    struc = make_simulation(small_spec, EngineConfig(neuron_model=neuron_model,
                                     schedule="structure_aware"), net=small_net)
    sc, ss = conv.init(), struc.init()
    for w in range(40):
        sc, blk_c = conv.window(sc)
        ss, blk_s = struc.window(ss)
        assert np.array_equal(np.asarray(blk_c), np.asarray(blk_s)), f"window {w}"
        assert np.array_equal(np.asarray(sc.ring), np.asarray(ss.ring)), f"ring {w}"
    assert int(sc.spike_count.sum()) > 0, "network must actually spike"


def test_deposit_variants_equivalent(small_spec, small_net):
    """One-hot-einsum and scatter-add delivery are interchangeable."""
    a = make_simulation(small_spec, EngineConfig(schedule="structure_aware",
                                 delivery_backend="onehot"), net=small_net)
    b = make_simulation(small_spec, EngineConfig(schedule="structure_aware",
                                 delivery_backend="scatter"), net=small_net)
    sa, sb = a.init(), b.init()
    for _ in range(10):
        sa, blk_a = a.window(sa)
        sb, blk_b = b.window(sb)
        assert np.array_equal(np.asarray(blk_a), np.asarray(blk_b))


def test_legacy_delivery_knobs_removed():
    """The deprecated pre-dispatch knobs (deposit_onehot / delivery,
    deprecated in the exchange-layer PR, removed in the sharded-table PR)
    are gone: delivery_backend is the single dispatch point."""
    with pytest.raises(TypeError):
        EngineConfig(deposit_onehot=True)
    with pytest.raises(TypeError):
        EngineConfig(delivery="event")
    assert EngineConfig().backend == "onehot"
    assert EngineConfig(delivery_backend="event").backend == "event"


def test_lif_ground_state_rate(small_spec, small_net):
    """The calibrated drive puts the LIF network near the MAM ground state
    (~2.5 spikes/s; we accept a generous band at this tiny scale)."""
    eng = make_simulation(small_spec, EngineConfig(neuron_model="lif"), net=small_net)
    st = eng.init()
    st, _ = eng.run(st, 500)  # 500 ms
    t_s = float(st.t) * small_spec.dt_ms / 1000.0
    rate = float(st.spike_count.sum()) / (small_spec.n_total * t_s)
    assert 0.5 < rate < 10.0, f"ground-state rate {rate:.2f} Hz out of band"


def test_ignore_and_fire_exact_rate():
    """Ignore-and-fire emits at exactly the configured rate (paper §4.2)."""
    spec = mam_benchmark_spec(n_areas=2, n_per_area=32, k_intra=4, k_inter=4,
                              rate_hz=10.0)
    net = build_network(spec, seed=12)
    eng = make_simulation(spec, EngineConfig(neuron_model="ignore_and_fire"), net=net)
    st = eng.init()
    st, _ = eng.run(st, 1000)  # 1 s
    rate = float(st.spike_count.sum()) / spec.n_total
    assert abs(rate - 10.0) < 0.11, rate


def test_heterogeneous_area_sizes_ghost_padding():
    """Heterogeneous areas pad to N_max with frozen ghosts that never fire."""
    spec = mam_benchmark_spec(n_areas=4, n_per_area=40, k_intra=4, k_inter=4,
                              area_size_cv=0.3, seed=7)
    net = build_network(spec, seed=12)
    sizes = spec.area_sizes()
    assert len(set(sizes.tolist())) > 1, "sizes should differ"
    eng = make_simulation(spec, EngineConfig(neuron_model="ignore_and_fire"), net=net)
    st = eng.init()
    st, _ = eng.run(st, 100)
    counts = np.asarray(st.spike_count)
    alive = np.asarray(net.alive)
    assert counts[~alive].sum() == 0, "ghost neurons must stay silent"
    assert counts[alive].sum() > 0


def test_mam_spec_properties():
    spec = mam_spec(scale=0.001)
    assert spec.n_areas == 32
    assert spec.delay_ratio == 10
    sizes = spec.area_sizes().astype(float)
    cv = sizes.std() / sizes.mean()
    assert 0.1 < cv < 0.3, f"MAM area-size CV {cv:.2f} (paper ~0.2)"
    rates = spec.area_rates()
    v2 = rates[list(MAM_AREA_NAMES).index("V2")]
    assert v2 > rates.mean() * 1.3, "V2 must be among the hottest areas"


def test_delay_tiers_respected(small_net, small_spec):
    d_intra = np.asarray(small_net.delay_intra)
    d_inter = np.asarray(small_net.delay_inter)
    assert d_intra.min() >= 1
    assert d_intra.max() <= small_spec.steps_intra_max
    assert d_inter.min() >= small_spec.delay_ratio, \
        "inter-area delays must respect the d_min_inter cutoff (eq. 1)"
    assert d_inter.max() < small_net.ring_len


@pytest.mark.parametrize("backend", ["onehot", "scatter", "pallas", "event"])
@pytest.mark.parametrize("schedule", ["conventional", "structure_aware"])
def test_delivery_backends_bit_identical(backend, schedule):
    """Tentpole invariant: every delivery backend (one-hot einsum, scatter-add,
    delay-resolved Pallas kernel, event-driven compaction) produces spike
    trains and ring buffers bit-identical to the reference -- weights on the
    1/256 grid make ring accumulation order-exact, so the backends may
    reorder sums freely."""
    spec = mam_benchmark_spec(n_areas=4, n_per_area=48, k_intra=8, k_inter=8,
                              rate_hz=30.0)
    net = build_network(spec, seed=91856, outgoing=True)
    ref = make_simulation(spec, EngineConfig(
        neuron_model="ignore_and_fire", schedule="conventional"), net=net)
    eng = make_simulation(spec, EngineConfig(
        neuron_model="ignore_and_fire", schedule=schedule,
        delivery_backend=backend, s_max_floor=64), net=net)
    s0, st = ref.init(), eng.init()
    for w in range(12):
        s0, blk_ref = ref.window(s0)
        st, blk = eng.window(st)
        assert np.array_equal(np.asarray(blk), np.asarray(blk_ref)), (backend, w)
        assert np.array_equal(np.asarray(s0.ring), np.asarray(st.ring)), (backend, w)
    assert int(st.overflow) == 0, "event packets must not drop spikes here"
    assert int(st.spike_count.sum()) > 0


@pytest.mark.parametrize("backend", ["pallas", "event"])
def test_delivery_backends_bit_identical_lif(backend):
    """The two kernel-backed backends also reproduce the LIF reference
    (float dynamics + Poisson drive) past the initial transient."""
    spec = mam_benchmark_spec(n_areas=4, n_per_area=48, k_intra=8, k_inter=8)
    net = build_network(spec, seed=12, outgoing=True)
    ref = make_simulation(spec, EngineConfig(
        neuron_model="lif", schedule="conventional"), net=net)
    eng = make_simulation(spec, EngineConfig(
        neuron_model="lif", schedule="structure_aware",
        delivery_backend=backend, s_max_floor=192), net=net)
    s0, st = ref.init(), eng.init()
    for w in range(30):
        s0, blk_ref = ref.window(s0)
        st, blk = eng.window(st)
        assert np.array_equal(np.asarray(blk), np.asarray(blk_ref)), (backend, w)
    assert int(st.overflow) == 0
    assert int(st.spike_count.sum()) > 0, "LIF must spike within 30 ms"


@pytest.mark.parametrize("backend", ["onehot", "scatter", "pallas", "event"])
def test_superstep_matches_legacy_window_bitwise(backend):
    """Tentpole: the fused D-cycle superstep (blocked ring read/clear, live
    window buffer, single-pass lumped inter delivery) is bit-identical to
    the legacy per-cycle window -- spike blocks AND rings -- for every
    backend, in both the scanned and the fully unrolled variant."""
    spec = mam_benchmark_spec(n_areas=4, n_per_area=48, k_intra=8, k_inter=8,
                              rate_hz=30.0)
    net = build_network(spec, seed=91856, outgoing=True)
    legacy = make_simulation(spec, EngineConfig(
        neuron_model="ignore_and_fire", schedule="structure_aware",
        delivery_backend=backend, s_max_floor=64, superstep=False), net=net)
    fused = make_simulation(spec, EngineConfig(
        neuron_model="ignore_and_fire", schedule="structure_aware",
        delivery_backend=backend, s_max_floor=64), net=net)
    unroll = make_simulation(spec, EngineConfig(
        neuron_model="ignore_and_fire", schedule="structure_aware",
        delivery_backend=backend, s_max_floor=64, superstep_unroll=True), net=net)
    sl, sf, su = legacy.init(), fused.init(), unroll.init()
    for w in range(12):
        sl, bl = legacy.window(sl)
        sf, bf = fused.window(sf)
        su, bu = unroll.window(su)
        assert np.array_equal(np.asarray(bl), np.asarray(bf)), (backend, w)
        assert np.array_equal(np.asarray(bl), np.asarray(bu)), (backend, w)
        assert np.array_equal(np.asarray(sl.ring), np.asarray(sf.ring)), (backend, w)
        assert np.array_equal(np.asarray(sl.ring), np.asarray(su.ring)), (backend, w)
    assert int(sl.spike_count.sum()) > 0


@pytest.mark.parametrize("neuron_model", ["ignore_and_fire", "lif"])
def test_fused_superstep_kernel_matches_reference(neuron_model):
    """The fused Pallas superstep kernel (kernels/cycle.py: membrane state and
    live ring slots VMEM-resident across the D unrolled cycles) reproduces
    the conventional per-cycle reference bitwise for both neuron models."""
    spec = mam_benchmark_spec(n_areas=4, n_per_area=48, k_intra=8, k_inter=8,
                              rate_hz=30.0)
    net = build_network(spec, seed=91856, outgoing=True)
    ref = make_simulation(spec, EngineConfig(
        neuron_model=neuron_model, schedule="conventional"), net=net)
    eng = make_simulation(spec, EngineConfig(
        neuron_model=neuron_model, schedule="structure_aware",
        delivery_backend="event", s_max_floor=64, superstep_kernel=True), net=net)
    s0, st = ref.init(), eng.init()
    for w in range(12):
        s0, blk_ref = ref.window(s0)
        st, blk = eng.window(st)
        assert np.array_equal(np.asarray(blk), np.asarray(blk_ref)), w
        assert np.array_equal(np.asarray(s0.ring), np.asarray(st.ring)), w
    assert int(st.overflow) == 0
    assert int(st.spike_count.sum()) > 0


def test_superstep_kernel_config_validation():
    with pytest.raises(ValueError):
        EngineConfig(schedule="conventional", superstep_kernel=True)
    with pytest.raises(ValueError):
        EngineConfig(superstep=False, superstep_kernel=True)
    with pytest.raises(ValueError):
        EngineConfig(schedule="conventional", superstep=True)
    # superstep=None/False with the conventional schedule stays valid.
    assert not EngineConfig(schedule="conventional").use_superstep
    assert not EngineConfig(schedule="conventional",
                            superstep=False).use_superstep


def test_ring_len_phase_aligned():
    """The ring length is padded to a multiple of D so window starts land on
    slot-block boundaries (the blocked read/clear's alignment contract)."""
    spec = mam_benchmark_spec(n_areas=4, n_per_area=48, k_intra=8, k_inter=8)
    assert spec.ring_len % spec.delay_ratio == 0
    assert spec.ring_len >= max(spec.steps_intra_max, spec.steps_inter_max) + 1
    net = build_network(spec, seed=12)
    assert net.ring_len % net.delay_ratio == 0


def test_overflow_identical_across_schedules_and_blocked_path():
    """Overflow accounting invariant: a forced-overflow run (tiny packet
    bound, synchronized firing) reports a *nonzero* spill count identical
    between the conventional schedule, the legacy per-cycle structure-aware
    window, and the blocked (superstep) delivery -- per-cycle packing is
    preserved inside the blocked packet, so the same spikes drop."""
    spec = mam_benchmark_spec(n_areas=2, n_per_area=64, k_intra=4, k_inter=4,
                              rate_hz=2000.0)  # interval 5: massed firing
    net = build_network(spec, seed=12, outgoing=True)
    counts = {}
    for name, kw in [
        ("conventional", dict(schedule="conventional")),
        ("legacy", dict(schedule="structure_aware", superstep=False)),
        ("superstep", dict(schedule="structure_aware")),
        ("superstep_unroll", dict(schedule="structure_aware",
                                  superstep_unroll=True)),
    ]:
        eng = make_simulation(spec, EngineConfig(
            neuron_model="ignore_and_fire", delivery_backend="event",
            s_max_headroom=0.0, s_max_floor=1, **kw), net=net)
        st = eng.init()
        for _ in range(5):
            st, _ = eng.window(st)
        counts[name] = int(st.overflow)
        assert int(st.spike_count.sum()) > 0
    assert counts["conventional"] > 0
    assert len(set(counts.values())) == 1, counts


def test_deliver_inter_block_equals_per_cycle():
    """delivery.deliver_inter_block(block) == D sequential deliver_inter
    calls, bitwise, for every backend (the single-pass lumped exchange)."""
    import jax.numpy as jnp

    from repro.core import delivery

    spec = mam_benchmark_spec(n_areas=4, n_per_area=48, k_intra=8, k_inter=8,
                              rate_hz=30.0)
    net = build_network(spec, seed=12, outgoing=True)
    A, n_pad = net.alive.shape
    D = net.delay_ratio
    rng = np.random.default_rng(0)
    block = jnp.asarray(rng.random((D, A * n_pad)) < 0.02, jnp.float32)
    ring0 = jnp.asarray(
        np.round(rng.normal(0, 8, (A, n_pad, net.ring_len))) / 256.0,
        jnp.float32)
    t0 = jnp.int32(3 * D)
    for backend in ["onehot", "scatter", "pallas", "event"]:
        want = ring0
        for s in range(D):
            want = delivery.deliver_inter(
                want, block[s], net, t0 + s, backend=backend, s_max=256)
        got = delivery.deliver_inter_block(
            ring0, block, net, t0, backend=backend, s_max=256)
        assert np.array_equal(np.asarray(got), np.asarray(want)), backend
    # The memory guard (per-cycle deposits inside the block beyond the
    # one-hot fold limit) must be bit-identical to the folded form.
    import repro.core.delivery as delivery_mod
    limit = delivery_mod.ONEHOT_FOLD_LIMIT
    try:
        delivery_mod.ONEHOT_FOLD_LIMIT = 0
        got = delivery.deliver_inter_block(ring0, block, net, t0,
                                           backend="onehot")
    finally:
        delivery_mod.ONEHOT_FOLD_LIMIT = limit
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_event_overflow_counter_reports_drops():
    """An undersized event packet drops spikes *visibly*: SimState.overflow
    counts them (the static analogue of NEST's spike-register resize)."""
    spec = mam_benchmark_spec(n_areas=2, n_per_area=64, k_intra=4, k_inter=4,
                              rate_hz=2000.0)
    net = build_network(spec, seed=12, outgoing=True)
    eng = make_simulation(spec, EngineConfig(
        neuron_model="ignore_and_fire", delivery_backend="event",
        s_max_headroom=0.0, s_max_floor=1), net=net)
    st = eng.init()
    for _ in range(5):
        st, _ = eng.window(st)
    assert int(st.spike_count.sum()) > 0
    assert int(st.overflow) > 0


def test_fused_lif_update_matches_jnp_chain():
    """The fused Pallas LIF kernel is a drop-in for the jnp update chain:
    bit-identical trajectories -- spikes and the v / i_syn / refrac state
    after every window -- on both schedules."""
    spec = mam_benchmark_spec(n_areas=4, n_per_area=48, k_intra=8, k_inter=8,
                              rate_hz=30.0)
    net = build_network(spec, seed=12)
    for schedule in ("conventional", "structure_aware"):
        plain, fused = (make_simulation(spec, EngineConfig(
            neuron_model="lif", delivery_backend="scatter", schedule=schedule,
            fused_update=f), net=net) for f in (False, True))
        sp, sf = plain.init(), fused.init()
        for w in range(30):
            sp, blk_p = plain.window(sp)
            sf, blk_f = fused.window(sf)
            assert np.array_equal(np.asarray(blk_p), np.asarray(blk_f)), w
            for field in ("v", "i_syn", "refrac"):
                assert np.array_equal(
                    np.asarray(getattr(sp.neuron, field)),
                    np.asarray(getattr(sf.neuron, field))), (schedule, w, field)
        assert int(sp.spike_count.sum()) > 0, "LIF must spike within 30 ms"


def test_network_delay_window_metadata():
    """build_network records the tight per-pathway delay windows that the
    delay-resolved (Pallas) backend iterates over."""
    spec = mam_benchmark_spec(n_areas=4, n_per_area=48, k_intra=8, k_inter=8)
    net = build_network(spec, seed=12)
    d_i, d_e = np.asarray(net.delay_intra), np.asarray(net.delay_inter)
    assert net.steps_lo_intra == d_i.min()
    assert net.steps_lo_intra + net.r_span_intra - 1 == d_i.max()
    assert net.steps_lo_inter == d_e.min()
    assert net.steps_lo_inter + net.r_span_inter - 1 == d_e.max()
    # the windows are what keeps the kernel narrow: both well under the ring
    assert net.r_span_intra < net.ring_len
    assert net.steps_lo_inter >= net.delay_ratio


def test_event_delivery_equals_dense_engine():
    """Beyond-paper optimization: event-driven delivery (compact fired
    neurons, scatter outgoing synapses) is bit-identical to the dense
    gather-matvec path -- weights live on the exact 1/256 grid."""
    from repro.core.engine import EngineConfig
    from repro.core.factory import make_simulation

    spec = mam_benchmark_spec(n_areas=4, n_per_area=48, k_intra=8, k_inter=8,
                              rate_hz=30.0)
    net = build_network(spec, seed=91856, outgoing=True)
    dense = make_simulation(spec, EngineConfig(
        neuron_model="ignore_and_fire", schedule="structure_aware",
        delivery_backend="onehot"), net=net)
    event = make_simulation(spec, EngineConfig(
        neuron_model="ignore_and_fire", schedule="structure_aware",
        delivery_backend="event"), net=net)
    sd, se = dense.init(), event.init()
    for w in range(25):
        sd, bd = dense.window(sd)
        se, be = event.window(se)
        assert np.array_equal(np.asarray(bd), np.asarray(be)), w
        assert np.array_equal(np.asarray(sd.ring), np.asarray(se.ring)), w
    assert int(sd.spike_count.sum()) > 100


@pytest.mark.parametrize("kw", [
    dict(), dict(superstep=False), dict(schedule="conventional"),
    dict(adaptive_exchange=True),
], ids=["superstep", "legacy", "conventional", "adaptive"])
def test_chunked_event_deposit_engine_bitwise(kw):
    """The event deposit's chunk loop fills the ring as the scatter backend
    does, bitwise, on every single-host exchange path. Outgoing tables
    ~4,400 wide make 8-slot chunks against packets of 126 (per area) and
    505 (per cycle) slots, with ~13 and ~51 live ids."""
    from repro.kernels import ops

    spec = mam_benchmark_spec(n_areas=4, n_per_area=64, k_intra=4200,
                              k_inter=4200, rate_hz=2000.0)
    net = build_network(spec, seed=5, outgoing=True)
    assert ops.deposit_chunk(net.tgt_intra.shape[-1], 126) == 8
    engines = [make_simulation(spec, EngineConfig(
        neuron_model="ignore_and_fire", delivery_backend=backend,
        s_max_floor=24, **kw), net=net) for backend in ("event", "scatter")]
    event, scatter = engines
    assert ops.DEPOSIT_CHUNK_SCOPE in event.window.lower(
        event.init()).as_text(debug_info=True)
    se, ss = event.init(), scatter.init()
    for w in range(5):
        se, be = event.window(se)
        ss, bs = scatter.window(ss)
        assert np.array_equal(np.asarray(be), np.asarray(bs)), w
        assert np.array_equal(np.asarray(se.ring), np.asarray(ss.ring)), w
        assert np.asarray(se.ring).any(), w
    assert int(se.overflow) == 0
    assert int(se.spike_count.sum()) > 2000
