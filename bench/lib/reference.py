"""Plain reference of the simulated network, independent of the program.

What decides ``correct``: the same TTI semantics as the simulator's
documented chain (TR 38.901 UMa pathloss, 3GPP horizontal sector pattern,
strongest-cell attachment, SINR -> CQI -> MCS -> spectral efficiency, the
alpha-fair PF split of each cell's resource blocks, stop-and-wait HARQ,
Poisson traffic, window movers and the birth-death UE process), written
out once in straightforward ``jax.numpy``.  It imports nothing of the
program and takes none of its tables: the deployment layout, power grid,
noise, CQI thresholds and MCS efficiencies are set here from the
configuration file.  Random draws follow the program's documented key
convention (``fold_in(key, 4 t + i)`` for mobility / fading / traffic /
HARQ, and the tagged churn lineage), so equal keys give equal draws.

``dtype`` is the precision of the whole computation: float32 is the
reference; bfloat16 is the control, the same reference one precision
below, which the comparison has to reject.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

C_LIGHT = 299_792_458.0
BOLTZMANN = 1.380649e-23
T0_KELVIN = 290.0
CHURN_TAG = 0x636872

#: SINR (dB) thresholds of CQI 1..15 and the MCS 0..28 spectral
#: efficiencies (TS 38.214 Table 5.1.3.1-1), as the simulator documents
#: its PHY abstraction (CQI -> MCS = round(28 CQI / 15)).
CQI_SINR_DB = (-3.25, -0.86, 1.22, 2.16, 3.78, 4.51, 6.42, 8.34, 8.92, 10.55,
               12.49, 13.45, 15.42, 17.27, 18.63)
MCS_SE = (0.2344, 0.3066, 0.3770, 0.4902, 0.6016, 0.7402, 0.8770, 1.0273,
          1.1758, 1.3262, 1.3281, 1.4766, 1.6953, 1.9141, 2.1602, 2.4063,
          2.5703, 2.5664, 2.7305, 3.0293, 3.3223, 3.6094, 3.9023, 4.2129,
          4.5234, 4.8164, 5.1152, 5.3320, 5.5547)


class RefCfg(NamedTuple):
    """The semantics of one cell, read from its configuration (hashable)."""

    n_cells: int
    n_sectors: int
    extent_m: float
    h_bs_m: float
    fc_GHz: float
    power_W: float
    noise_w: float            # per frequency chunk
    n_freq: int
    n_subbands: int
    n_rb: int                 # resource blocks per subband
    rb_per_chunk: int
    rb_bw_hz: float
    coherence_rb: int
    attach_on_mean: bool
    per_tti_fading: bool
    fairness_p: float
    pf_ewma: float
    tti_s: float
    harq_bler: float
    harq_max_retx: int
    harq_comb_db: float
    traffic: str              # full_buffer | poisson
    pkt_rate_hz: float
    pkt_bits: float
    move_step_m: float        # 0: static geometry
    n_move: int
    churn_rate_hz: float      # 0: fixed population
    churn_life_s: float
    churn_max_arrivals: int
    churn_newborn_bits: float
    phi3db_deg: float
    a_max_db: float


def ref_cfg(params: dict, *, per_tti_fading: bool,
            churn: Optional[dict]) -> RefCfg:
    """Build the reference's view of a cell from its parameter dict."""
    p = params
    n_sub = p.get("n_subbands", 1)
    n_rb = p.get("n_rb", 12)
    n_rbs = p.get("n_rb_subbands", 1)
    bw = p.get("bandwidth_Hz", 20e6)
    noise = p.get("noise_power_W")
    if noise is None:
        noise = BOLTZMANN * T0_KELVIN * bw * 10 ** (9.0 / 10)
    n = p["n_ues"]
    frac = p.get("mobility_move_frac")
    step = p.get("mobility_step_m") or 0.0
    tp = p.get("traffic_params", {})
    rayleigh = bool(p.get("rayleigh_fading", False))
    return RefCfg(
        n_cells=p["n_cells"], n_sectors=p.get("n_sectors", 1),
        extent_m=p["extent_m"], h_bs_m=p.get("h_bs_m", 25.0),
        fc_GHz=p.get("pathloss_params", {}).get("fc_GHz", 3.5),
        power_W=p["power_W"], noise_w=noise / (n_sub * n_rbs),
        n_freq=n_sub * n_rbs, n_subbands=n_sub, n_rb=n_rb, rb_per_chunk=n_rb // n_rbs,
        rb_bw_hz=bw / n_sub / n_rb, coherence_rb=p.get("coherence_rb", 4),
        attach_on_mean=rayleigh and p.get("attach_ignores_fading", True),
        per_tti_fading=per_tti_fading, fairness_p=p.get("fairness_p", 0.0),
        pf_ewma=p.get("pf_ewma", 0.05), tti_s=p.get("tti_s", 1e-3),
        harq_bler=p.get("harq_bler", 0.0),
        harq_max_retx=p.get("harq_max_retx", 3),
        harq_comb_db=p.get("harq_comb_gain_db", 3.0),
        traffic=p.get("traffic_model", "full_buffer"),
        pkt_rate_hz=tp.get("arrival_rate_hz", 200.0),
        pkt_bits=tp.get("packet_size_bits", 12_000.0),
        move_step_m=step,
        n_move=(max(1, int(round(frac * n))) if step and frac and frac < 1
                else n),
        churn_rate_hz=churn["arrival_rate_hz"] if churn else 0.0,
        churn_life_s=churn["mean_lifetime_s"] if churn else 1.0,
        churn_max_arrivals=churn["max_arrivals_per_tti"] if churn else 0,
        churn_newborn_bits=churn.get("newborn_backlog_bits", 0.0)
        if churn else 0.0,
        phi3db_deg=p.get("antenna_phi_3dB_deg", 65.0),
        a_max_db=p.get("antenna_A_max_dB", 30.0))


def layout(rc: RefCfg):
    """Cell positions (n_cells, 3) and boresights: a hexagonal grid of
    sites centred in the square, ISD = extent / (2 rings + 1), each site
    repeated per sector with sector s pointing at s * 2 pi / n_sectors."""
    n_sites = max(1, rc.n_cells // rc.n_sectors)
    rings = 0
    while 1 + 3 * rings * (rings + 1) < n_sites:
        rings += 1
    isd = rc.extent_m / (2 * rings + 1) if rings else rc.extent_m
    xy = []
    for q in range(-rings, rings + 1):
        for r in range(max(-rings, -q - rings), min(rings, -q + rings) + 1):
            xy.append((isd * (q + r / 2.0), isd * r * 0.8660254037844386))
    sites = np.asarray(xy, np.float32)[:n_sites]
    sites = np.concatenate(
        [sites, np.full((n_sites, 1), rc.h_bs_m, np.float32)], axis=1)
    sites = jnp.asarray(sites) + jnp.asarray(
        [rc.extent_m / 2, rc.extent_m / 2, 0.0])
    C = jnp.repeat(sites, rc.n_sectors, axis=0)
    sector = jnp.arange(n_sites * rc.n_sectors) % rc.n_sectors
    bore = sector.astype(jnp.float32) * (2.0 * jnp.pi / rc.n_sectors)
    return C, bore


def _log10(x):
    return jnp.log10(jnp.maximum(x, 1e-9))


def link_gain(rc: RefCfg, U, C, bore):
    """Unfaded linear gain (n, m): UMa pathloss x sector pattern."""
    dx = U[:, None, 0] - C[None, :, 0]
    dy = U[:, None, 1] - C[None, :, 1]
    dz = U[:, None, 2] - C[None, :, 2]
    d2d = jnp.sqrt(dx * dx + dy * dy)
    d3d = jnp.sqrt(d2d * d2d + dz * dz)
    h_bs, h_ut = C[None, :, 2], U[:, 2][:, None]
    fc = jnp.asarray(rc.fc_GHz, U.dtype)
    d_bp = 4.0 * (h_bs - 1.0) * (h_ut - 1.0) * (rc.fc_GHz * 1e9) / C_LIGHT
    pl1 = 28.0 + 22.0 * _log10(d3d) + 20.0 * _log10(fc)
    pl2 = (28.0 + 40.0 * _log10(d3d) + 20.0 * _log10(fc)
           - 9.0 * _log10(d_bp ** 2 + (h_bs - h_ut) ** 2))
    los = jnp.where(d2d <= d_bp, pl1, pl2)
    nlos = 13.54 + 39.08 * _log10(d3d) + 20.0 * _log10(fc) - 0.6 * (
        h_ut - 1.5)
    g = jnp.power(10.0, -0.1 * jnp.maximum(los, nlos))
    if rc.n_sectors > 1:
        az = jnp.arctan2(dy, dx)
        phi = az - bore[None, :]
        off = jnp.arctan2(jnp.sin(phi), jnp.cos(phi))
        phi3 = jnp.deg2rad(jnp.asarray(rc.phi3db_deg, U.dtype))
        att_db = 0.0 - jnp.minimum(12.0 * (off / phi3) ** 2, rc.a_max_db)
        g = g * jnp.power(10.0, 0.1 * att_db)
    return g


def spectral_efficiency(gamma):
    """(se, cqi) of a linear SINR tensor."""
    thr = jnp.asarray(CQI_SINR_DB, jnp.float32).astype(gamma.dtype)
    sinr_db = 10.0 * jnp.log10(jnp.maximum(gamma, 1e-12))
    cqi = jnp.sum(sinr_db[..., None] >= thr, axis=-1).astype(jnp.int32)
    mcs = jnp.clip(jnp.round(cqi.astype(jnp.float32) * 28.0 / 15.0), 0,
                   28).astype(jnp.int32)
    table = jnp.asarray(MCS_SE, jnp.float32).astype(gamma.dtype)
    return jnp.where(cqi > 0, table[mcs], 0.0), cqi


def radio(rc: RefCfg, U, C, bore, P, fad):
    """(se (n, K), a (n,)) for positions U; ``fad`` None (no fading) or
    the (n, m, K) per-chunk fading power."""
    G0 = link_gain(rc, U, C, bore)
    G = G0[:, :, None] if fad is None else G0[:, :, None] * fad
    R = G * P[None, :, :]
    meas = (G0[:, :, None] * P[None, :, :] if rc.attach_on_mean else R)
    a = jnp.argmax(meas.sum(axis=2), axis=1).astype(jnp.int32)
    w = jnp.take_along_axis(R, a[:, None, None], axis=1)[:, 0, :]
    u = R.sum(axis=1) - w
    se, _ = spectral_efficiency(w / (rc.noise_w + u))
    return se, a


def radio_blocked(rc: RefCfg, U, C, bore, P, block: int):
    """:func:`radio` without fading over row blocks (bounded memory)."""
    n = U.shape[0]
    if n <= block:
        return radio(rc, U, C, bore, P, None)
    assert n % block == 0, (n, block)
    se, a = jax.lax.map(lambda u: radio(rc, u, C, bore, P, None),
                        U.reshape(n // block, block, 3))
    return se.reshape(n, -1), a.reshape(n)


def fading(rc: RefCfg, key, n: int, dtype):
    """Per-TTI block Rayleigh power per CQI chunk: one Exp(1) draw per
    coherence block of RBs, repeated over its RBs and averaged over each
    chunk's RBs."""
    n_rb = rc.n_subbands * rc.n_rb
    n_blocks = -(-n_rb // rc.coherence_rb)
    draw = jax.random.exponential(key, (n, rc.n_cells, n_blocks),
                                  dtype=jnp.float32)
    per_rb = jnp.repeat(draw, rc.coherence_rb, axis=2)[:, :, :n_rb]
    per_rb = per_rb.astype(dtype)
    return per_rb.reshape(n, rc.n_cells, rc.n_freq, -1).mean(axis=-1)


class RefState(NamedTuple):
    U: jnp.ndarray
    backlog: jnp.ndarray
    pf_avg: jnp.ndarray
    harq_bits: jnp.ndarray
    harq_retx: jnp.ndarray
    active: jnp.ndarray       # all True without churn
    key: jnp.ndarray
    t: jnp.ndarray


def segment_sum(x, a, m):
    return jnp.zeros((m,) + x.shape[1:], x.dtype).at[a].add(x)


def tti(rc: RefCfg, C, bore, P, s: RefState, dtype, block: int):
    """One TTI.  Returns (state, tput (n,), kpis dict)."""
    n, m = s.U.shape[0], rc.n_cells
    t = s.t
    k_mob, k_fad, k_tr, k_harq = (jax.random.fold_in(s.key, 4 * t + i)
                                  for i in range(4))
    U, buf, avg = s.U, s.backlog, s.pf_avg
    hbits, hretx, act = s.harq_bits, s.harq_retx, s.active
    # -- birth-death process: departures, then Poisson arrivals into the
    # lowest free slots with fresh uniform positions
    if rc.churn_rate_hz > 0:
        kc = jax.random.fold_in(s.key, CHURN_TAG)
        k_birth, k_death, k_pos, _ = (jax.random.fold_in(kc, 4 * t + i)
                                      for i in range(4))
        p_dep = min(1.0, rc.tti_s / rc.churn_life_s)
        act = act & ~(jax.random.bernoulli(k_death, p_dep, (n,)) & act)
        n_arr = jnp.minimum(
            jax.random.poisson(k_birth, rc.churn_rate_hz * rc.tti_s, ()),
            rc.churn_max_arrivals).astype(jnp.int32)
        free = ~act
        rank = jnp.cumsum(free.astype(jnp.int32)) - 1
        born = free & (rank < n_arr)
        act = act | born
        zero = jnp.zeros((), dtype)
        buf = jnp.where(act, buf, zero)
        avg = jnp.where(act, avg, zero)
        hbits = jnp.where(act, hbits, zero)
        hretx = jnp.where(act, hretx, 0)
        buf = jnp.where(born, jnp.asarray(rc.churn_newborn_bits, dtype), buf)
        avg = jnp.where(born, zero, avg)
        hbits = jnp.where(born, zero, hbits)
        hretx = jnp.where(born, 0, hretx)
        fresh = jax.random.uniform(k_pos, (rc.churn_max_arrivals, 2),
                                   minval=0.0, maxval=rc.extent_m)
        brank = jnp.clip(jnp.cumsum(born.astype(jnp.int32)) - 1, 0,
                         rc.churn_max_arrivals - 1)
        xy = jnp.where(born[:, None], fresh[brank].astype(dtype), U[:, :2])
        U = jnp.concatenate([xy, U[:, 2:3]], axis=1)
    # -- window movers: exactly n_move UEs from a random circular offset
    if rc.move_step_m:
        k_off, k_step = jax.random.split(k_mob)
        start = jax.random.randint(k_off, (), 0, n)
        d = jax.random.uniform(k_step, (rc.n_move, 2), minval=-rc.move_step_m,
                               maxval=rc.move_step_m)
        j = (jnp.arange(n) - start) % n
        moved = j < rc.n_move
        disp = jnp.where(moved[:, None],
                         d[jnp.clip(j, 0, rc.n_move - 1)].astype(dtype), 0.0)
        xy = jnp.clip(U[:, :2] + disp, 0.0, rc.extent_m)
        U = jnp.concatenate([xy, U[:, 2:3]], axis=1)
    # -- radio
    if rc.per_tti_fading:
        se, a = radio(rc, U, C, bore, P, fading(rc, k_fad, n, dtype))
    else:
        se, a = radio_blocked(rc, U, C, bore, P, block)
    # -- traffic
    if rc.traffic == "poisson":
        cnt = jax.random.poisson(jax.random.fold_in(k_tr, t),
                                 rc.pkt_rate_hz * rc.tti_s, (n,))
        arr = cnt.astype(dtype) * jnp.asarray(rc.pkt_bits, dtype)
        buf = buf + jnp.where(act, arr, 0.0)
    harq = rc.harq_bler > 0
    pending = hbits > 0 if harq else jnp.zeros((n,), bool)
    # -- alpha-fair PF split of each cell's RBs on every chunk
    demand = ((buf > 0) | pending) & act
    on = demand[:, None] & (se > 0)
    fp = rc.fairness_p
    alpha = min((1.0 + fp) / max(1.0 - fp, 1e-6), 63.0)
    log_w = (jnp.log(jnp.maximum(rc.rb_bw_hz * se, 1e-12))
             - alpha * jnp.log(jnp.maximum(avg[:, None], 1e-3)))
    log_w = jnp.where(on, log_w, -jnp.inf)
    cmax = jnp.full((m, rc.n_freq), -jnp.inf, dtype).at[a].max(log_w)
    w = jnp.where(on, jnp.exp(log_w - cmax[a]), 0.0)
    den = segment_sum(w, a, m)[a]
    alloc = rc.rb_per_chunk * jnp.where(den > 0, w / jnp.maximum(den, 1e-30),
                                        0.0)
    # -- delivery, capped by the backlog
    drain = jnp.where(pending, 0.0, buf)
    cap = alloc * rc.rb_bw_hz * se * rc.tti_s
    tot = cap.sum(axis=-1)
    scale = jnp.where(tot > 0, jnp.minimum(drain / jnp.maximum(tot, 1e-30),
                                           1.0), 0.0)
    tb_new = (cap * scale[:, None]).sum(axis=1)
    granted = alloc.sum(axis=1) > 0
    if harq:
        tb = jnp.where(pending, hbits, tb_new)
        trying = granted & (tb > 0)
        attempt = jnp.where(pending, hretx, 0)
        gain = 10.0 ** (rc.harq_comb_db / 10.0)
        p_fail = jnp.clip(rc.harq_bler * gain ** (-attempt.astype(dtype)),
                          0.0, 1.0)
        uni = jax.random.uniform(k_harq, (n,)).astype(dtype)
        ok = (uni >= p_fail) & trying
        fail = trying & ~ok
        n_fail = attempt + 1
        keep = (fail & (n_fail <= rc.harq_max_retx)) | (pending & ~granted)
        bits = jnp.where(ok, tb, 0.0)
        acks, nacks = ok.sum(), fail.sum()
        retx = (pending & trying).sum()
        dropped = jnp.where(fail & (n_fail > rc.harq_max_retx), tb, 0.0).sum()
        hbits = jnp.where(keep, tb, 0.0)
        hretx = jnp.where(keep, jnp.where(fail, n_fail, hretx), 0)
        buf = jnp.maximum(buf - tb_new, 0.0)
    else:
        bits = tb_new
        acks, nacks = (bits > 0).sum(), jnp.int32(0)
        retx, dropped = jnp.int32(0), jnp.zeros((), dtype)
        buf = jnp.maximum(buf - bits, 0.0)
    tput = bits / rc.tti_s
    avg = (1.0 - rc.pf_ewma) * avg + rc.pf_ewma * tput
    n_act = act.sum()
    s2 = (tput * tput).sum()
    kpis = dict(
        served_bits=segment_sum(bits, a, m),
        granted_rb=segment_sum(alloc.sum(axis=-1), a, m),
        harq_acks=acks, harq_nacks=nacks, harq_retx=retx,
        dropped_bits=dropped,
        buffer_bits=jnp.where(jnp.isfinite(buf), buf, 0.0).sum(),
        jain=jnp.where(s2 > 0, tput.sum() ** 2 / (jnp.maximum(n_act, 1) * s2),
                       0.0),
        active_ues=n_act, attach=a)
    return RefState(U, buf, avg, hbits, hretx, act, s.key, t + 1), tput, kpis


def cast_state(s: RefState, dtype) -> RefState:
    f = lambda x: jnp.asarray(x, dtype)
    return s._replace(U=f(s.U), backlog=f(s.backlog), pf_avg=f(s.pf_avg),
                      harq_bits=f(s.harq_bits))


@partial(jax.jit, static_argnums=(0, 5, 6, 7))
def _tti_jit(rc, C, bore, P, s, dtype, block, batched):
    f = lambda st: tti(rc, C, bore, P, st, dtype, block)
    return jax.vmap(f)(s) if batched else f(s)


def run(rc: RefCfg, s0: RefState, n_tti: int, dtype=jnp.float32,
        block: int = 250_000, batched: bool = False):
    """Advance ``n_tti`` TTIs from ``s0``.

    Returns (final state, tput stacked (n_tti, ...n), per-TTI KPI dicts
    stacked on axis 0, each UE's serving cell under ``attach``).
    ``batched`` maps over a leading drop axis of the state (independent
    drops of one deployment)."""
    C, bore = layout(rc)
    C, bore = C.astype(dtype), bore.astype(dtype)
    P = jnp.full((rc.n_cells, rc.n_freq), rc.power_W / rc.n_freq,
                 jnp.float32).astype(dtype)
    s = cast_state(s0, dtype)
    tputs, kpis = [], []
    for _ in range(n_tti):
        s, tput, k = _tti_jit(rc, C, bore, P, s, jnp.dtype(dtype), block,
                              batched)
        tputs.append(tput.astype(jnp.float32))
        kpis.append(k)
    tput = jnp.stack(tputs, axis=1 if batched else 0)
    stacked = {k: jnp.stack([d[k] for d in kpis], axis=1 if batched else 0)
               for k in kpis[0]}
    return s, tput, stacked


def drop(key, n: int, extent_m: float, h_ut_m: float, dtype=jnp.float32):
    """A drop's UE field from its key: the topology stream is the first of
    ``split(key, 3)`` (the program's documented convention, for a
    simulator's seed key and for a topology-resampling reset alike), drawn
    uniform over the square at UE height."""
    k_topo = jax.random.split(key, 3)[0]
    xy = jax.random.uniform(k_topo, (n, 2), minval=0.0, maxval=extent_m)
    U = jnp.concatenate([xy, jnp.full((n, 1), h_ut_m)], axis=1)
    return U.astype(dtype).astype(jnp.float32)


def summarize(kpis: dict, tti_s: float) -> dict:
    """The twin's chunk summary (the KPI dict a twin client reads), from
    stacked per-TTI KPIs."""
    k = {name: np.asarray(v, np.float64) for name, v in kpis.items()}
    n_tti = max(1, k["jain"].size)
    attempts = k["harq_acks"].sum() + k["harq_nacks"].sum()
    busiest = k["served_bits"].sum(axis=0).max()
    return {
        "served_mbits": k["served_bits"].sum() / 1e6,
        "mean_cell_load_rb": k["granted_rb"].mean(),
        "harq_acks": k["harq_acks"].sum(),
        "harq_nacks": k["harq_nacks"].sum(),
        "harq_nack_rate": (k["harq_nacks"].sum() / attempts
                           if attempts else 0.0),
        "harq_retx": k["harq_retx"].sum(),
        "dropped_mbits": k["dropped_bits"].sum() / 1e6,
        "mean_buffer_mbits": k["buffer_bits"].mean() / 1e6,
        "mean_jain": k["jain"].mean(),
        "busiest_cell_mbps": busiest / (n_tti * tti_s) / 1e6,
        "mean_active_ues": k["active_ues"].mean(),
    }

