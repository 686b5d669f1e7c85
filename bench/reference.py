"""The plain reference: the network's own rules, written down once more.

It imports nothing of the program under test. From a configuration file
and a seed it draws the connectivity again (the counter-based rules the
configuration states: fixed in-degree, uniform sources, 80/20 weights on the
1/256 grid, Gaussian delays with cutoffs), and it integrates the LIF neurons
(exact propagators, calibrated Poisson drive keyed on ``(seed, t, gid)``).

``check`` judges a recorded spike raster the way a served model's tokens are
judged: it runs the reference once over the whole raster, teacher-forced.
Every neuron's input at cycle ``t`` is what the recorded spikes before ``t``
deliver through the reference's own synapses; the reference then integrates
every neuron through every cycle and says whether it fires. A raster that
agrees with the reference at every neuron and cycle, and ends in the same
neuron state, is the network's trajectory: the earliest wrong bit would be
the first place where the two disagree. The sums are exact (weights on the
1/256 grid), so the comparison is bitwise and its limit is 0.

The connectivity is drawn on the device in 32-bit integer arithmetic (the
source picks and weights are exact there). Gaussian delays need float64
near a rounding boundary: the device computes them in float32, flags every
synapse that lies within ``DELAY_MARGIN_Z`` deviates of a boundary, and the
host recomputes the flagged ones in float64 exactly as the rule states.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

# Float32 Box-Muller on a TPU v5e lands within 1.4e-4 of the float64 normal
# deviate (12M synapses sampled on the chip); every synapse within this many
# deviates (times std / dt in steps) of a rounding boundary is recomputed on
# the host in float64.
DELAY_MARGIN_Z = 2e-3
# Spikes deposited per device call, and cycles integrated per device call.
SPIKE_BATCH = 512
CHUNK_CYCLES = 100

_TAG_SRC_INTRA = 1
_TAG_SRC_AREA = 2
_TAG_SRC_IDX = 3
_TAG_W_INTRA = 4
_TAG_W_INTER = 5
_TAG_D_INTRA_U1 = 6
_TAG_D_INTRA_U2 = 7
_TAG_D_INTER_U1 = 8
_TAG_D_INTER_U2 = 9


@dataclasses.dataclass(frozen=True)
class Params:
    """Everything the reference needs, read from a configuration dict."""

    sizes: tuple[int, ...]
    rates: tuple[float, ...]
    k_intra: int
    k_inter: int
    dt_ms: float
    d_min_inter_ms: float
    delay_intra_mean_ms: float
    delay_intra_std_ms: float
    delay_inter_mean_ms: float
    delay_inter_std_ms: float
    delay_intra_max_ms: float
    delay_inter_max_ms: float
    exc_fraction: float
    w_exc: float
    g: float
    ext_rate_hz: float
    w_ext: float
    tau_m_ms: float
    tau_syn_ms: float
    c_m_pf: float
    t_ref_ms: float
    v_th_mv: float
    v_reset_mv: float
    stim: float = 1.0

    @property
    def n_areas(self) -> int:
        return len(self.sizes)

    @property
    def n_pad(self) -> int:
        return max(self.sizes)

    @property
    def n_rows(self) -> int:
        return self.n_areas * self.n_pad

    @property
    def delay_ratio(self) -> int:
        return int(round(self.d_min_inter_ms / self.dt_ms))

    @property
    def steps_intra_max(self) -> int:
        return int(round(self.delay_intra_max_ms / self.dt_ms))

    @property
    def steps_inter_max(self) -> int:
        return int(round(self.delay_inter_max_ms / self.dt_ms))

    @property
    def max_delay(self) -> int:
        return max(self.steps_intra_max, self.steps_inter_max)

    def alive_rows(self) -> np.ndarray:
        """Padded global ids ``area * n_pad + i`` of the live neurons."""
        return np.concatenate([a * self.n_pad + np.arange(n)
                               for a, n in enumerate(self.sizes)])

    def propagators(self) -> tuple[float, float, float]:
        """(p11, p21, p22) of the exact iaf_psc_exp integration."""
        tm, ts, dt, cm = self.tau_m_ms, self.tau_syn_ms, self.dt_ms, self.c_m_pf
        p11 = float(np.exp(-dt / ts))
        p22 = float(np.exp(-dt / tm))
        if abs(tm - ts) < 1e-12:
            p21 = float(dt / cm * np.exp(-dt / tm))
        else:
            p21 = float((tm * ts) / (cm * (tm - ts))
                        * (np.exp(-dt / tm) - np.exp(-dt / ts)))
        return p11, p21, p22


def params_from_config(cfg: dict, stim: float = 1.0) -> Params:
    """Read the reference's parameters from a configuration dict."""
    areas = cfg["areas"]
    net, lif = cfg["network"], cfg["lif"]
    return Params(
        sizes=tuple(int(a["n_neurons"]) for a in areas),
        rates=tuple(float(a["rate_hz"]) for a in areas),
        stim=float(stim),
        **{f.name: net[f.name] if f.name in net else lif[f.name]
           for f in dataclasses.fields(Params)
           if f.name not in ("sizes", "rates", "stim")})


# ---------------------------------------------------------------------------
# Counter-based draws
# ---------------------------------------------------------------------------


def _mix32(x):
    import jax.numpy as jnp

    u = jnp.uint32
    x = x + u(0x9E3779B9)
    x = (x ^ (x >> u(16))) * u(0x21F0AAAD)
    x = (x ^ (x >> u(15))) * u(0x735A2D97)
    return x ^ (x >> u(15))


def _np_mix32(x: np.ndarray) -> np.ndarray:
    x = x.astype(np.uint32, copy=True)
    x += np.uint32(0x9E3779B9)
    x = (x ^ (x >> np.uint32(16))) * np.uint32(0x21F0AAAD)
    x = (x ^ (x >> np.uint32(15))) * np.uint32(0x735A2D97)
    return x ^ (x >> np.uint32(15))


def _tag_word(seed: int, tag: int) -> int:
    return (int(seed) + int(tag) * 0x85EBCA6B) & 0xFFFFFFFF


def _hash(words, tag: int, idx):
    """uint32 hash of ``(seed, tag, flat synapse index)``; idx < 2**32.
    ``words[tag]`` is the seed's word for the tag (see ``_tag_word``)."""
    return _mix32(_mix32(_mix32(idx + words[tag])))


def _np_hash(seed: int, tag: int, idx: np.ndarray) -> np.ndarray:
    idx = np.asarray(idx, dtype=np.uint64)
    lo = (idx & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    hi = (idx >> np.uint64(32)).astype(np.uint32)
    return _np_mix32(_np_mix32(_np_mix32(lo + np.uint32(_tag_word(seed, tag)))
                               + hi))


def _grid_weight(h, w_exc: int):
    """``round_half_even((0.5 + (h + 0.5) / 2**32) * w_exc * 256)`` exactly,
    in uint32 arithmetic, as a multiple of 1/256 (float32)."""
    import jax.numpy as jnp

    u = jnp.uint32
    a = h >> u(24)
    m = ((h & u(0xFFFFFF)) * u(2) + u(1)) * u(w_exc)
    q = u(w_exc * 128) + a * u(w_exc) + (m >> u(25))
    r = m & u((1 << 25) - 1)
    half = u(1 << 24)
    q = q + ((r > half) | ((r == half) & ((q & u(1)) == u(1)))).astype(u)
    return q.astype(jnp.float32) * jnp.float32(1.0 / 256.0)


def _delay_f32(h1, h2, mean_ms, std_ms, dt_ms, lo, hi):
    """Float32 delays in steps, and the mask of those near a rounding
    boundary (which the host recomputes in float64)."""
    import jax.numpy as jnp

    f32 = jnp.float32
    scale = f32(2.0 ** -32)
    small = h1 < jnp.uint32(1 << 31)
    log_lo = jnp.log((h1.astype(f32) + f32(0.5)) * scale)
    log_hi = jnp.log1p(-((jnp.uint32(0xFFFFFFFF) - h1).astype(f32)
                         + f32(0.5)) * scale)
    log_u1 = jnp.where(small, log_lo, log_hi)
    u2 = (h2.astype(f32) + f32(0.5)) * scale
    z = jnp.sqrt(f32(-2.0) * log_u1) * jnp.cos(f32(2.0 * math.pi) * u2)
    d = (f32(mean_ms) + f32(std_ms) * z) / f32(dt_ms)
    margin = f32(DELAY_MARGIN_Z * std_ms / dt_ms)
    near = jnp.abs(d - jnp.floor(d) - f32(0.5)) < margin
    steps = jnp.clip(jnp.round(d), lo, hi).astype(jnp.int32)
    return steps, near


def _np_delay(seed, tag1, tag2, idx, mean_ms, std_ms, dt_ms, lo, hi):
    """The rule in float64: Box-Muller on two counter uniforms, rounded to
    the dt grid and clipped to ``[lo, hi]`` steps."""
    u1 = (_np_hash(seed, tag1, idx).astype(np.float64) + 0.5) * (2.0 ** -32)
    u2 = (_np_hash(seed, tag2, idx).astype(np.float64) + 0.5) * (2.0 ** -32)
    z = np.sqrt(-2.0 * np.log(u1)) * np.cos(2.0 * np.pi * u2)
    d = (mean_ms + std_ms * z) / dt_ms
    return np.clip(np.round(d), lo, hi).astype(np.int32)


@dataclasses.dataclass
class Tables:
    """Outgoing synapses of every neuron, sorted by source (CSR)."""

    offsets: object      # [n_rows + 1] int32
    packed: object       # [S] int32: target row * 128 + delay
    weight: object       # [S] float32
    counts: np.ndarray   # [n_rows] int64 on the host: synapses per source
    k_out: int           # static row width of a spike's gather
    n_fixed: int         # delays recomputed on the host in float64


def _pathway(p: Params, seed: int, pathway: str):
    """Device tables ``(src row, target row, weight, delay)`` of one pathway,
    flattened over the live target rows."""
    import jax
    import jax.numpy as jnp

    k = p.k_intra if pathway == "intra" else p.k_inter
    rows = p.alive_rows()
    if p.n_rows * k >= 2 ** 32:
        raise ValueError("flat synapse index exceeds 32 bits")
    sizes = np.asarray(p.sizes, np.int64)
    thr = np.maximum(1, (p.exc_fraction * sizes).astype(np.int64))
    area_of = rows // p.n_pad
    if p.steps_inter_max > 127:
        raise ValueError("delays above 127 steps do not fit the packing")
    w_exc = int(p.w_exc)
    if w_exc != p.w_exc or not 0 < w_exc < 128:
        raise ValueError("the exact weight rule needs an integer w_exc < 128")
    if pathway == "intra":
        tags = (_TAG_W_INTRA, _TAG_D_INTRA_U1, _TAG_D_INTRA_U2)
        dl = (p.delay_intra_mean_ms, p.delay_intra_std_ms, 1,
              p.steps_intra_max)
    else:
        tags = (_TAG_W_INTER, _TAG_D_INTER_U1, _TAG_D_INTER_U2)
        dl = (p.delay_inter_mean_ms, p.delay_inter_std_ms, p.delay_ratio,
              p.steps_inter_max)
    # Inter sources: a uniform area among the others (all to all), then a
    # uniform neuron of it.
    a = p.n_areas
    allowed = np.array([[s for s in range(a) if s != t] or [0]
                        for t in range(a)], np.int32)

    def draw(rows, area_of, words):
        u = jnp.uint32
        idx = (rows.astype(u)[:, None] * u(k)
               + jnp.arange(k, dtype=u)[None, :])
        size_t = jnp.asarray(sizes, u)[area_of][:, None]
        if pathway == "intra":
            src_in = (_hash(words, _TAG_SRC_INTRA, idx) % size_t).astype(
                jnp.int32)
            src_area = jnp.broadcast_to(area_of[:, None], idx.shape)
        else:
            pick = _hash(words, _TAG_SRC_AREA, idx) % u(a - 1)
            src_area = jnp.asarray(allowed)[area_of[:, None],
                                            pick.astype(jnp.int32)]
            src_in = (_hash(words, _TAG_SRC_IDX, idx)
                      % jnp.asarray(sizes, u)[src_area]).astype(jnp.int32)
        exc = src_in < jnp.asarray(thr, jnp.int32)[src_area]
        mag = _grid_weight(_hash(words, tags[0], idx), w_exc)
        w = jnp.where(exc, mag, jnp.float32(-p.g) * mag)
        d, near = _delay_f32(_hash(words, tags[1], idx),
                             _hash(words, tags[2], idx), dl[0], dl[1],
                             p.dt_ms, dl[2], dl[3])
        src = src_area * p.n_pad + src_in
        tgt = jnp.broadcast_to(rows[:, None], idx.shape)
        return (src.reshape(-1), tgt.reshape(-1), w.reshape(-1),
                d.reshape(-1), near.reshape(-1))

    if k == 0:
        z = jnp.zeros((0,), jnp.int32)
        return z, z, jnp.zeros((0,), jnp.float32), z, 0
    words = jnp.asarray([_tag_word(seed, t) for t in range(10)], jnp.uint32)
    src, tgt, w, d, near = jax.jit(draw)(
        jnp.asarray(rows, jnp.int32), jnp.asarray(area_of, jnp.int32), words)
    # Float64 for the synapses near a rounding boundary. The buffers are
    # sized in powers of two so that the programs do not change with the
    # seed.
    n_near = int(near.sum())
    if n_near:
        cap = 1 << max(10, (n_near - 1).bit_length())
        pos = np.array(_nonzero(near, cap))
        valid = pos >= 0
        flat = (rows[pos[valid] // k].astype(np.uint64) * np.uint64(k)
                + (pos[valid] % k).astype(np.uint64))
        exact = np.zeros(cap, np.int32)
        exact[valid] = _np_delay(seed, tags[1], tags[2], flat, dl[0], dl[1],
                                 p.dt_ms, dl[2], dl[3])
        pos[~valid] = d.shape[0]
        d = _set(d, jnp.asarray(pos, jnp.int32), jnp.asarray(exact))
    return src, tgt, w, d, n_near


def _nonzero(mask, cap: int):
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda m: jnp.nonzero(m, size=cap, fill_value=-1)[0])(mask)


def _set(x, pos, vals):
    import jax

    return jax.jit(lambda x, i, v: x.at[i].set(v, mode="drop"),
                   donate_argnums=(0,))(x, pos, vals)


def _k_out_bound(p: Params) -> int:
    """A seed-independent bound on any neuron's out-degree (mean + 6 sd + 8
    per source area), so the gather width, and with it the compiled
    programs, does not change with the seed."""
    a = p.n_areas
    sizes = np.asarray(p.sizes, np.float64)
    out = p.k_intra * np.ones(a)
    if p.k_inter and a > 1:
        out = out + (sizes.sum() - sizes) * p.k_inter / (a - 1) / sizes
    return int(max(math.ceil(m + 6 * math.sqrt(m)) for m in out)) + 8


def build_tables(p: Params, seed: int) -> Tables:
    """Draw both pathways on the device and sort them by source."""
    import jax
    import jax.numpy as jnp

    parts = [_pathway(p, seed, pw) for pw in ("intra", "inter")]
    n_fixed = sum(x[4] for x in parts)
    src = jnp.concatenate([x[0] for x in parts])
    packed = jnp.concatenate([x[1] * 128 + x[3] for x in parts])
    weight = jnp.concatenate([x[2] for x in parts])
    del parts

    def sort(src, packed, weight):
        src, packed, weight = jax.lax.sort((src, packed, weight), num_keys=1)
        offsets = jnp.searchsorted(
            src, jnp.arange(p.n_rows + 1, dtype=jnp.int32), side="left")
        return offsets.astype(jnp.int32), packed, weight

    offsets, packed, weight = jax.jit(sort, donate_argnums=(1, 2))(
        src, packed, weight)
    counts = np.diff(np.asarray(offsets).astype(np.int64))
    k_out = _k_out_bound(p)
    if counts.max(initial=0) > k_out:
        k_out = int(-(-counts.max() // 256) * 256)
    return Tables(offsets, packed, weight, counts, k_out, n_fixed)


# ---------------------------------------------------------------------------
# The teacher-forced check
# ---------------------------------------------------------------------------


def _drive(p: Params, drive_seed: int, t, dtype):
    """External Poisson drive of every padded row at cycle ``t``."""
    import jax.numpy as jnp

    n = p.n_rows
    gids = jnp.arange(n, dtype=jnp.int32)
    rate = np.zeros(n, np.float32)
    for a, (size, r) in enumerate(zip(p.sizes, p.rates)):
        rate[a * p.n_pad: a * p.n_pad + size] = r
    rate = jnp.asarray(rate) * (p.ext_rate_hz / 2.5)
    if p.stim != 1.0:
        rate = rate * jnp.full((n,), p.stim, jnp.float32)
    prob = rate * (p.dt_ms * 1e-3)
    h = _mix32(_mix32(_mix32(jnp.asarray(drive_seed, jnp.uint32))
                      + gids.astype(jnp.uint32))
               + jnp.asarray(t, jnp.uint32))
    u = h.astype(jnp.float32) * jnp.float32(1.0 / 4294967296.0)
    return ((u < prob).astype(jnp.float32) * p.w_ext).astype(dtype)


def _programs(p: Params, k_out: int, dtype_name: str):
    """The two device programs of the check: ``deposit`` scatters a batch
    of recorded spikes through their outgoing synapses into the input
    buffer; ``integrate`` runs the neurons through one chunk of cycles."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(dtype_name)
    n, c = p.n_rows, CHUNK_CYCLES
    span = c + p.max_delay + 1
    alive_np = np.zeros(n, bool)
    alive_np[p.alive_rows()] = True
    p11, p21, p22 = p.propagators()
    t_ref = int(round(p.t_ref_ms / p.dt_ms))

    def deposit(buf, offsets, packed, weight, t_rel, src, valid):
        start = offsets[src]
        cnt = offsets[src + 1] - start
        j = jnp.arange(k_out, dtype=jnp.int32)
        ok = valid[:, None] & (j[None, :] < cnt[:, None])
        pos = jnp.where(ok, start[:, None] + j[None, :], 0)
        pk = packed[pos]
        slot = t_rel[:, None] + pk % 128
        flat = jnp.where(ok, slot * n + pk // 128, span * n)
        return buf.at[flat.reshape(-1)].add(
            jnp.where(ok, weight[pos], 0.0).reshape(-1), mode="drop")

    def integrate(state, buf, t0, raster, n_valid, drive_seed):
        alive = jnp.asarray(alive_np)

        def step(st, j):
            v, i_syn, refrac = st
            i_in = jax.lax.dynamic_slice(buf, (j * n,), (n,)).astype(dtype)
            i_in = i_in + _drive(p, drive_seed, t0 + j, dtype)
            refractory = refrac > 0
            i_new = i_syn * p11 + i_in
            # ``v * p22 + i_syn * p21``, the second product rounded on its
            # own (the select hides it from multiply-add fusion): a compiler
            # that fuses can then only form fma(v, p22, i_syn * p21), the
            # form the program's compiled window takes on a CPU.
            q = jnp.where(jnp.isnan(i_syn), i_syn, i_syn * p21)
            v_prop = v * p22 + q
            v_new = jnp.where(refractory, p.v_reset_mv, v_prop)
            spk = (v_new >= p.v_th_mv) & alive & ~refractory
            v_out = jnp.where(spk, p.v_reset_mv, v_new)
            r_out = jnp.where(spk, jnp.int32(t_ref),
                              jnp.maximum(refrac - 1, 0))
            live = j < n_valid
            new = (jnp.where(live, v_out, v), jnp.where(live, i_new, i_syn),
                   jnp.where(live, r_out, refrac))
            miss = jnp.sum((spk != raster[j]) & live, dtype=jnp.int32)
            return new, (miss, jnp.sum(spk & live, dtype=jnp.int32))

        state, (miss, fired) = jax.lax.scan(
            step, state, jnp.arange(c, dtype=jnp.int32))
        shifted = jnp.concatenate(
            [buf[c * n:], jnp.zeros((c * n,), buf.dtype)])
        return state, shifted, miss, fired

    return (jax.jit(deposit, donate_argnums=(0,)),
            jax.jit(integrate, donate_argnums=(1,)), span)


def check(p: Params, tables: Tables, seed: int, raster: np.ndarray,
          final: dict, *, v0: np.ndarray | None = None,
          dtype: str = "float32") -> dict:
    """Teacher-force the reference through ``raster`` and compare.

    ``raster`` is ``[T, n_rows]`` bool (padded ids, cycles from 0), and
    ``final`` the program's neuron state after cycle ``T`` (``v``,
    ``i_syn``, ``refrac`` as arrays of ``n_rows``), ``v0`` the initial
    membrane potentials (zero by default). ``dtype`` is the
    precision of the neuron integration (``bfloat16`` for the control).
    Returns the counts that decide ``correct``: ``raster_mismatch``
    (neuron-cycles where the reference and the raster disagree, ghost rows
    included), ``state_mismatch`` (live neurons whose final state differs
    in any bit), and the mismatches of each cycle.
    """
    import jax.numpy as jnp

    n, c = p.n_rows, CHUNK_CYCLES
    deposit, integrate, span = _programs(p, tables.k_out, dtype)
    drive_seed = jnp.uint32(int(seed) % (1 << 32))
    dt = jnp.dtype(dtype)
    v_init = np.zeros(n, np.float32) if v0 is None else v0
    state = (jnp.asarray(v_init).astype(dt), jnp.zeros((n,), dt),
             jnp.zeros((n,), jnp.int32))
    buf = jnp.zeros((span * n,), jnp.float32)
    t_total = raster.shape[0]
    miss_all, fired_all = [np.zeros(0, np.int64)], [np.zeros(0, np.int64)]
    for t0 in range(0, t_total, c):
        chunk = raster[t0:t0 + c]
        n_valid = chunk.shape[0]
        if n_valid < c:
            chunk = np.concatenate([chunk, np.zeros((c - n_valid, n), bool)])
        ts, srcs = np.nonzero(chunk)
        for b in range(0, len(ts), SPIKE_BATCH):
            m = len(ts[b:b + SPIKE_BATCH])
            tb = np.zeros(SPIKE_BATCH, np.int32)
            sb = np.zeros(SPIKE_BATCH, np.int32)
            vb = np.zeros(SPIKE_BATCH, bool)
            tb[:m], sb[:m], vb[:m] = ts[b:b + m], srcs[b:b + m], True
            buf = deposit(buf, tables.offsets, tables.packed, tables.weight,
                          jnp.asarray(tb), jnp.asarray(sb), jnp.asarray(vb))
        state, buf, miss, fired = integrate(
            state, buf, jnp.int32(t0), jnp.asarray(chunk),
            jnp.int32(n_valid), drive_seed)
        miss_all.append(np.asarray(miss)[:n_valid])
        fired_all.append(np.asarray(fired)[:n_valid])
    rows = p.alive_rows()
    differs = np.zeros(len(rows), bool)
    for name, got in zip(("v", "i_syn", "refrac"), state):
        want = np.asarray(final[name]).reshape(-1)[rows]
        got = np.asarray(got)[rows].astype(want.dtype)
        differs |= (got.view(np.uint8).reshape(len(rows), -1)
                    != want.view(np.uint8).reshape(len(rows), -1)).any(axis=1)
    miss = np.concatenate(miss_all)
    return {
        "raster_mismatch": int(miss.sum()),
        "state_mismatch": int(differs.sum()),
        "mismatch_per_cycle": miss,
        "reference_spikes": int(np.concatenate(fired_all).sum()),
        "final_state": {k: np.asarray(x) for k, x in
                        zip(("v", "i_syn", "refrac"), state)},
    }
