"""Pallas kernel validation: shape/dtype sweeps against the pure-jnp oracles.

Kernels run in interpret=True on CPU (the TPU lowering is the target; the
semantics are validated here). Float comparisons are against *jitted* oracles
-- jit and eager differ by FMA contraction (1 ulp), the kernels match jit
bitwise.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

LIF_KW = dict(p11=0.8187308, p21=3.617e-4, p22=0.9900498,
              v_th=15.0, v_reset=0.0, t_ref_steps=20)


@pytest.mark.parametrize("n", [64, 129, 1000, 4096, 8192])
def test_lif_update_matches_oracle(n):
    rng = np.random.default_rng(n)
    v = jnp.asarray(rng.normal(5, 4, n), jnp.float32)
    i_syn = jnp.asarray(rng.normal(150, 80, n), jnp.float32)
    refrac = jnp.asarray(rng.integers(0, 5, n), jnp.int32)
    i_in = jnp.asarray(rng.normal(40, 30, n), jnp.float32)
    alive = jnp.asarray(rng.random(n) < 0.9)
    out_k = ops.lif_update(v, i_syn, refrac, i_in, alive, **LIF_KW)
    oracle = jax.jit(functools.partial(ref.lif_update_ref, **LIF_KW))
    out_r = oracle(v, i_syn, refrac, i_in, alive)
    for name, a, b in zip(("v", "i_syn", "refrac", "spk"), out_k, out_r):
        assert np.array_equal(np.asarray(a), np.asarray(b)), name


def test_lif_update_2d_state():
    """The ops wrapper flattens arbitrary shapes (engines use [A, n_pad])."""
    rng = np.random.default_rng(0)
    shape = (4, 96)
    v = jnp.asarray(rng.normal(5, 4, shape), jnp.float32)
    i_syn = jnp.zeros(shape, jnp.float32)
    refrac = jnp.zeros(shape, jnp.int32)
    i_in = jnp.asarray(rng.normal(0, 10, shape), jnp.float32)
    alive = jnp.ones(shape, bool)
    out = ops.lif_update(v, i_syn, refrac, i_in, alive, **LIF_KW)
    assert out[0].shape == shape
    assert out[3].dtype == jnp.bool_


def test_lif_refractory_semantics():
    """A spiking neuron resets and stays clamped for t_ref steps."""
    kw = dict(LIF_KW, t_ref_steps=3)
    v = jnp.asarray([20.0] * 128, jnp.float32)  # above threshold after prop
    i_syn = jnp.zeros(128, jnp.float32)
    refrac = jnp.zeros(128, jnp.int32)
    alive = jnp.ones(128, bool)
    v, i_syn, refrac, spk = ops.lif_update(v, i_syn, refrac,
                                           jnp.zeros(128), alive, **kw)
    assert bool(spk.all()) and float(v.max()) == 0.0 and int(refrac[0]) == 3
    for step in range(3):
        v, i_syn, refrac, spk = ops.lif_update(
            v, i_syn, refrac, jnp.full((128,), 1e6), alive, **kw)
        assert not bool(spk.any()), f"refractory step {step} must not spike"
    v, i_syn, refrac, spk = ops.lif_update(
        v, i_syn, refrac, jnp.full((128,), 1e6), alive, **kw)
    assert bool(spk.all()), "after refractory period the huge input must fire"


@pytest.mark.parametrize("n,k,n_src,lo,span", [
    (64, 8, 128, 1, 5),
    (300, 16, 512, 10, 9),
    (256, 64, 256, 1, 30),
    (128, 3, 64, 2, 2),
    (1024, 32, 2048, 10, 91),
])
def test_spike_deliver_matches_oracle(n, k, n_src, lo, span):
    rng = np.random.default_rng(k)
    spikes = jnp.asarray(rng.random(n_src) < 0.1, jnp.float32)
    src = jnp.asarray(rng.integers(0, n_src, (n, k)), jnp.int32)
    w = jnp.asarray(np.round(rng.normal(0, 64, (n, k))) / 256.0, jnp.float32)
    d = jnp.asarray(rng.integers(lo, lo + span, (n, k)), jnp.int32)
    out_k = ops.spike_deliver(spikes, src, w, d, steps_lo=lo, r_span=span)
    oracle = jax.jit(functools.partial(ref.spike_deliver_ref,
                                       steps_lo=lo, r_span=span))
    assert np.array_equal(np.asarray(out_k), np.asarray(oracle(spikes, src, w, d)))


def test_spike_deliver_then_apply_contrib_equals_ring_deposit():
    """kernel contributions rolled into the ring == reference deposit."""
    from repro.core import ring_buffer
    rng = np.random.default_rng(3)
    n, k, r, lo, span = 96, 8, 16, 1, 6
    spikes = jnp.asarray(rng.random(n) < 0.3, jnp.float32)
    src = jnp.asarray(rng.integers(0, n, (n, k)), jnp.int32)
    w = jnp.asarray(np.round(rng.normal(0, 64, (n, k))) / 256.0, jnp.float32)
    d = jnp.asarray(rng.integers(lo, lo + span, (n, k)), jnp.int32)
    ring = jnp.asarray(np.round(rng.normal(0, 8, (n, r))) / 256.0, jnp.float32)
    t = jnp.int32(11)
    contrib = ops.spike_deliver(spikes, src, w, d, steps_lo=lo, r_span=span)
    got = ops.apply_contrib(ring, contrib, t, lo)
    want = ring_buffer.deposit(ring, w * spikes[src], d, t)
    assert np.allclose(np.asarray(got), np.asarray(want), atol=1e-6)


def test_event_deliver_equals_dense():
    """Event-driven (compaction+scatter) delivery == dense delivery."""
    from repro.core import ring_buffer
    rng = np.random.default_rng(5)
    n_src, n_tgt, k_out, r = 200, 160, 12, 24
    spikes = jnp.asarray(rng.random(n_src) < 0.15)
    tgt = jnp.asarray(rng.integers(0, n_tgt, (n_src, k_out)), jnp.int32)
    w = jnp.asarray(np.round(rng.normal(0, 64, (n_src, k_out))) / 256.0,
                    jnp.float32)
    d = jnp.asarray(rng.integers(1, r - 1, (n_src, k_out)), jnp.int32)
    ring = jnp.zeros((n_tgt, r), jnp.float32)
    got = ops.event_deliver(ring, spikes, tgt, w, d, jnp.int32(7), s_max=128)
    # dense oracle: scatter every synapse of every fired source
    want = np.zeros((n_tgt, r), np.float32)
    sp = np.asarray(spikes)
    for s in range(n_src):
        if sp[s]:
            for kk in range(k_out):
                want[int(tgt[s, kk]), (7 + int(d[s, kk])) % r] += float(w[s, kk])
    assert np.allclose(np.asarray(got), want)


def test_event_deliver_ids_matches_event_deliver():
    """The id-packet entry point (the sparse wire format's receive side) ==
    compacting locally and delivering: same scatter core, same result."""
    rng = np.random.default_rng(11)
    n_src, n_tgt, k_out, r, s_max = 120, 96, 6, 16, 32
    spikes = jnp.asarray(rng.random(n_src) < 0.1)
    tgt = jnp.asarray(rng.integers(0, n_tgt, (n_src, k_out)), jnp.int32)
    w = jnp.asarray(np.round(rng.normal(0, 64, (n_src, k_out))) / 256.0,
                    jnp.float32)
    d = jnp.asarray(rng.integers(1, r - 1, (n_src, k_out)), jnp.int32)
    ring = jnp.zeros((n_tgt, r), jnp.float32)
    t = jnp.int32(3)
    want = ops.event_deliver(ring, spikes, tgt, w, d, t, s_max=s_max)
    # hand-built packet: fired ids in arbitrary order + sentinel padding
    fired = np.flatnonzero(np.asarray(spikes))
    rng.shuffle(fired)
    packet = np.full(s_max, n_src, np.int32)
    packet[: len(fired)] = fired
    got = ops.event_deliver_ids(ring, jnp.asarray(packet), tgt, w, d, t)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_event_deliver_ids_absorbs_padding():
    """Sentinel ids (>= N_src) and table padding rows (tgt=-1, w=0) must not
    touch any real target row."""
    n = 32
    tgt = jnp.full((n, 2), -1, jnp.int32)        # all padding rows
    w = jnp.zeros((n, 2), jnp.float32)
    d = jnp.ones((n, 2), jnp.int32)
    ring = jnp.zeros((n, 4), jnp.float32)
    ids = jnp.asarray([0, 5, n, n + 7], jnp.int32)  # 2 real, 2 sentinel
    out = ops.event_deliver_ids(ring, ids, tgt, w, d, jnp.int32(0))
    assert float(jnp.abs(out).max()) == 0.0


@pytest.mark.parametrize("n,size,density", [
    (64, 8, 0.1), (1000, 16, 0.0), (1000, 16, 0.9),  # overflow case included
    (257, 4, 0.02), (8192, 128, 0.001),
])
def test_sized_nonzero_matches_jnp(n, size, density):
    """The searchsorted compaction == jnp.nonzero(size=, fill_value=) exactly,
    including which indices survive under overflow (first `size` by index).
    It replaces the sized-nonzero sort in every event path (~13x faster on
    CPU at N~6k: the sort was the hidden per-cycle cost of compaction)."""
    rng = np.random.default_rng(n + size)
    mask = jnp.asarray(rng.random(n) < density)
    want = jnp.nonzero(mask, size=size, fill_value=n)[0]
    got = ops.sized_nonzero(mask, size=size, fill=n)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_event_deliver_block_matches_per_cycle_ids():
    """The single-pass blocked receive == D sequential per-cycle id scatters
    (same packets, slots offset by the implicit step), bitwise."""
    rng = np.random.default_rng(7)
    n_src, n_tgt, k_out, r, s_max, d_win = 120, 96, 6, 20, 8, 10
    tgt = jnp.asarray(rng.integers(0, n_tgt, (n_src, k_out)), jnp.int32)
    w = jnp.asarray(np.round(rng.normal(0, 64, (n_src, k_out))) / 256.0,
                    jnp.float32)
    d = jnp.asarray(rng.integers(1, r - 1, (n_src, k_out)), jnp.int32)
    ids = np.full((d_win, s_max), n_src, np.int32)
    for s in range(d_win):
        k = rng.integers(0, s_max + 1)
        ids[s, :k] = rng.choice(n_src, k, replace=False)
    ids = jnp.asarray(ids)
    ring = jnp.zeros((n_tgt, r), jnp.float32)
    t0 = jnp.int32(13)
    want = ring
    for s in range(d_win):
        want = ops.event_deliver_ids(want, ids[s], tgt, w, d, t0 + s)
    got = ops.event_deliver_block(ring, ids, tgt, w, d, t0)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def _one_shot_deposit(ring, ids, tgt, w, d, t0, tgt_map=None):
    """The event deposit as one static scatter of every packet slot: the
    oracle of the chunked deposit (fill and holes add +0.0 to row 0)."""
    n_tgt, r = ring.shape
    d_win, s_max = ids.shape
    steps = jnp.repeat(jnp.arange(d_win, dtype=jnp.int32), s_max)
    ids = ids.reshape(-1)
    valid = ids < tgt.shape[0]
    safe = jnp.where(valid, ids, 0)
    tg = tgt[safe] if tgt_map is None else tgt_map(tgt[safe])
    ok = valid[:, None] & (tg >= 0) & (tg < n_tgt)
    vals = jnp.where(ok, w[safe], 0.0)
    slots = jnp.mod(t0 + steps[:, None] + d[safe].astype(jnp.int32), r)
    return ring.at[jnp.where(ok, tg, 0).reshape(-1),
                   slots.reshape(-1)].add(vals.reshape(-1))


# K_out wide enough that a chunk is the minimum of 8 slots; S = 21 is not a
# multiple of it, so the last chunk runs over padding.
CHUNK_K_OUT, CHUNK_N_SRC, CHUNK_S = 4100, 48, 21


def _chunk_case(case, rng):
    """``(ids [D, S], tgt_map, int8 delays, K_out)`` for one chunked-deposit
    case."""
    c, fill = ops.deposit_chunk(CHUNK_K_OUT, CHUNK_S), CHUNK_N_SRC
    live = lambda k: rng.choice(CHUNK_N_SRC, k, replace=False)
    ids = np.full((1, CHUNK_S), fill, np.int32)
    tgt_map, int8, k_out = None, False, CHUNK_K_OUT
    if case == "empty":
        pass
    elif case == "one":
        ids[0, 0] = live(1)[0]
    elif case == "chunk":
        ids[0, :c] = live(c)
    elif case == "chunk_plus_one":
        ids[0, :c + 1] = live(c + 1)
    elif case == "full":
        ids[0] = live(CHUNK_S)
    elif case == "rows":
        ids = np.full((4, CHUNK_S), fill, np.int32)
        for row, k in enumerate((0, 3, CHUNK_S, c + 2)):
            ids[row, :k] = live(k)
    elif case == "holes":
        # Two per-device packets of 10 slots side by side, as a tiled
        # all_gather lays them out: live runs with fill between them.
        ids = np.full((2, 20), fill + 3, np.int32)
        ids[0, :2], ids[0, 10:17] = live(2), live(7)
        ids[1, 10:11] = live(1)
    elif case == "tgt_map":
        ids[0, :c + 3] = live(c + 3)
        tgt_map = lambda g: jnp.where(g % 3 == 0, -1, g // 2)
    elif case == "int8_delays":
        ids[0, :2 * c] = live(2 * c)
        int8 = True
    elif case == "narrow_table":
        # A table narrow enough that one chunk covers the packet: C = S.
        ids[0, :CHUNK_S - 2] = live(CHUNK_S - 2)
        k_out = 32
    return ids, tgt_map, int8, k_out


@pytest.mark.parametrize("case", [
    "empty", "one", "chunk", "chunk_plus_one", "full", "rows", "holes",
    "tgt_map", "int8_delays", "narrow_table"])
def test_event_deliver_block_chunked_matches_one_shot(case):
    """The chunked deposit, which stops after the chunk holding each
    block's last live id, == one static scatter of every slot, bitwise; its
    trip count is ceil(last live position / C)."""
    rng = np.random.default_rng(sum(map(ord, case)))
    assert ops.deposit_chunk(CHUNK_K_OUT, CHUNK_S) == 8
    assert ops.deposit_chunk(3337, 1030) == 16
    assert ops.deposit_chunk(32, CHUNK_S) == CHUNK_S
    ids, tgt_map, int8, k_out = _chunk_case(case, rng)
    c = ops.deposit_chunk(k_out, ids.shape[1])
    n_tgt, r = 40, 12
    shape = (CHUNK_N_SRC, k_out)
    tgt = jnp.asarray(rng.integers(-1, n_tgt, shape), jnp.int32)
    w = jnp.asarray(np.round(rng.normal(0, 64, shape)) / 256.0, jnp.float32)
    d = jnp.asarray(rng.integers(1, r - 1, shape),
                    jnp.int8 if int8 else jnp.int32)
    ring = jnp.asarray(np.round(rng.normal(0, 512, (n_tgt, r))) / 256.0,
                       jnp.float32)
    t0 = jnp.int32(5)

    live = ids < CHUNK_N_SRC
    last = max((np.flatnonzero(row)[-1] + 1 if row.any() else 0)
               for row in live)
    trips = ops.deposit_trips(jnp.asarray(ids), CHUNK_N_SRC, k_out)
    assert int(trips) == -(-last // c)

    deposit = jax.jit(functools.partial(
        ops.event_deliver_block, tgt_map=tgt_map))
    got = deposit(ring, jnp.asarray(ids), tgt, w, d, t0)
    want = jax.jit(functools.partial(_one_shot_deposit, tgt_map=tgt_map))(
        ring, jnp.asarray(ids), tgt, w, d, t0)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    if case == "empty":
        assert np.array_equal(np.asarray(got), np.asarray(ring))


def _while_eqns(jaxpr):
    """Every ``while`` equation of a jaxpr, nested ones included."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "while":
            out.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out.extend(_while_eqns(sub))
    return out


def test_event_deliver_per_area_matches_one_shot():
    """Per-area deposits through one chunk loop, which runs to the last
    live id of any area, == per-area one-shot scatters; the loop's
    predicate is one scalar, not one flag per area."""
    rng = np.random.default_rng(19)
    a, n, r = 3, CHUNK_N_SRC, 10
    shape = (a, n, CHUNK_K_OUT)
    tgt = jnp.asarray(rng.integers(-1, n, shape), jnp.int32)
    w = jnp.asarray(np.round(rng.normal(0, 64, shape)) / 256.0, jnp.float32)
    d = jnp.asarray(rng.integers(1, r - 1, shape), jnp.int8)
    ids = np.full((a, CHUNK_S), n, np.int32)
    for row, k in enumerate((1, 0, 17)):
        ids[row, :k] = rng.choice(n, k, replace=False)
    ids = jnp.asarray(ids)
    ring = jnp.zeros((a, n, r), jnp.float32)
    t = jnp.int32(2)

    def deliver(ring, ids):
        return ops.event_deliver_per_area(ring, ids, tgt, w, d, t)

    got = jax.jit(deliver)(ring, ids)
    for k in range(a):
        want = _one_shot_deposit(ring[k], ids[k][None], tgt[k], w[k], d[k], t)
        assert np.array_equal(np.asarray(got[k]), np.asarray(want)), k
    (loop,) = _while_eqns(jax.make_jaxpr(deliver)(ring, ids).jaxpr)
    cond = loop.params["cond_jaxpr"].jaxpr
    assert cond.outvars[0].aval.shape == (), cond
    hlo = jax.jit(deliver).lower(ring, ids).as_text(debug_info=True)
    assert ops.DEPOSIT_CHUNK_SCOPE in hlo


def test_superstep_kernels_match_unfused_window():
    """kernels/cycle.py: one fused window (D cycles of update + intra
    delivery on a VMEM-resident live buffer) == the unfused op chain."""
    from repro.core.neuron import counter_uniform
    from repro.kernels.lif_update import lif_step_math

    rng = np.random.default_rng(3)
    a, n, k, d_win, lo, span = 3, 96, 8, 5, 1, 6
    w_width = d_win + lo + span - 1
    src = jnp.asarray(rng.integers(0, n, (a, n, k)), jnp.int32)
    w = jnp.asarray(np.round(rng.normal(0, 64, (a, n, k))) / 256.0, jnp.float32)
    delay = jnp.asarray(rng.integers(lo, lo + span, (a, n, k)), jnp.int32)
    alive = jnp.asarray(rng.random((a, n)) < 0.9)
    fut0 = jnp.asarray(
        np.round(rng.normal(0, 512, (a, n, w_width))) / 256.0, jnp.float32)
    gids = jnp.arange(a * n, dtype=jnp.int32).reshape(a, n)
    drive_p = jnp.full((a, n), 0.3, jnp.float32)
    v = jnp.asarray(rng.normal(5, 4, (a, n)), jnp.float32)
    i_syn = jnp.asarray(rng.normal(100, 50, (a, n)), jnp.float32)
    refrac = jnp.asarray(rng.integers(0, 3, (a, n)), jnp.int32)
    kw = dict(LIF_KW, t_ref_steps=3)
    t0 = jnp.int32(0)

    got = ops.superstep_lif(
        v, i_syn, refrac, fut0, drive_p, gids, alive, src, w, delay, t0,
        d_win=d_win, steps_lo=lo, r_span=span, seed=11, w_ext=88.0, **kw)

    # unfused oracle: the shared per-cycle LIF math + dense masked deposit.
    # (The update is lif_step_math itself, not the split lif_update kernel.
    # XLA:CPU contracts v*p22 + i_syn*p21 into a fused multiply-add in
    # every path; an interpret-mode kernel inlined into this D-cycle loop
    # contracts it differently from the superstep kernel in the last bit
    # of some v. The engines' fused and jnp updates agree bitwise in v,
    # i_syn and refrac: test_system.test_fused_lif_update_matches_jnp_chain
    # on the CPU, chip_smoke.py on the chip.)
    @jax.jit
    def oracle(v, i_syn, refrac, fut):
        spikes = []
        for s in range(d_win):
            u = counter_uniform(11, t0 + s, gids)
            i_in = fut[..., s] + (u < drive_p).astype(jnp.float32) * 88.0
            v, i_syn, refrac, spk = lif_step_math(
                v, i_syn, refrac, i_in, alive, **kw)
            spikes.append(spk)
            vals = w * spk.astype(jnp.float32)[
                jnp.arange(a)[:, None, None], src]
            for j in range(span):
                col = jnp.sum(
                    jnp.where(delay - lo == j, vals, 0.0), axis=-1)
                fut = fut.at[..., s + lo + j].add(col)
        return v, i_syn, refrac, fut, jnp.stack(spikes, axis=1)

    want = oracle(v, i_syn, refrac, fut0)
    names = ("v", "i_syn", "refrac", "fut", "spikes")
    for name, g, ww in zip(names, got, want):
        g = np.asarray(g)
        ww = np.asarray(ww.astype(jnp.int32) if name == "spikes" else ww)
        assert np.array_equal(g, ww), name


def test_event_deliver_s_max_bound():
    """With fewer events than s_max the result is exact; the buffer bound is
    the static analogue of NEST's spike-register resizing."""
    n = 64
    spikes = jnp.zeros(n, bool).at[:5].set(True)
    tgt = jnp.zeros((n, 2), jnp.int32)
    w = jnp.ones((n, 2), jnp.float32)
    d = jnp.ones((n, 2), jnp.int32)
    ring = jnp.zeros((n, 4), jnp.float32)
    out = ops.event_deliver(ring, spikes, tgt, w, d, jnp.int32(0), s_max=8)
    assert float(out[0, 1]) == 10.0  # 5 events x 2 synapses x w=1


@pytest.mark.parametrize("b,s,h,hkv,dh,window,klen", [
    (2, 64, 4, 2, 16, 0, 64),
    (1, 128, 8, 4, 32, 17, 128),
    (2, 64, 4, 2, 16, 0, 40),      # partially valid keys (decode-like)
    (1, 64, 2, 2, 16, 5, 64),      # MHA + tight window
])
def test_flash_attention_matches_streaming_oracle(b, s, h, hkv, dh, window, klen):
    """Fused flash kernel (VMEM-resident tiles) == jnp streaming attention."""
    import repro.models.layers as L
    from repro.kernels.flash_attention import flash_attention_pallas

    rng = np.random.default_rng(h * s + window)
    q = jnp.asarray(rng.normal(size=(b, s, h, dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, hkv, dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, hkv, dh)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    out_k = flash_attention_pallas(q, k, v, jnp.int32(window),
                                   jnp.int32(klen), bq=32, bk=32)
    out_r = L._streaming_attention(q, k, v, pos, pos, jnp.int32(klen), window)
    assert float(jnp.abs(out_k - out_r).max()) < 2e-5
