"""Run the reference over a sampled call and compute the compared numbers.

A driver hands over one :class:`Sample`: a call of the measured window
drawn from the seed (reservoir sampling over every call, so each is as
likely), the state it started from, and what the timed path produced.
The reference advances the same state through the same TTIs; the numbers
of ``compare`` measure the disagreement.  With ``control=True`` the
reference computed in bfloat16 takes the program's place.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import compare, reference


class Sample(NamedTuple):
    rc: reference.RefCfg
    s0: reference.RefState     # the sampled call's starting state
    n_tti: int
    batched: bool              # leading drop axis
    prog: dict                 # tput, t, pf_avg, backlog, U[, kpis]
    U0: np.ndarray             # the program's UE field after set-up
    drop_keys: jnp.ndarray     # the drop key(s) the seed gives
    h_ut_m: float


def as_ref_state(state) -> reference.RefState:
    """The reference's view of an ``EpisodeState``-like pytree."""
    act = state.active
    if act is None:
        act = jnp.ones(state.backlog.shape, bool)
    return reference.RefState(
        U=state.U, backlog=state.backlog, pf_avg=state.pf_avg,
        harq_bits=state.harq_bits, harq_retx=state.harq_retx, active=act,
        key=state.key, t=state.t)


def outputs(state, tput, kpis: Optional[dict] = None) -> dict:
    out = {k: np.asarray(getattr(state, k))
           for k in ("t", "pf_avg", "backlog", "U")}
    out["tput"] = np.asarray(tput, np.float32)
    if kpis is not None:
        out["kpis"] = dict(kpis)
    return out


def _reference(sample: Sample, dtype) -> dict:
    s, tput, kpis = reference.run(sample.rc, sample.s0, sample.n_tti,
                                  dtype=dtype, batched=sample.batched)
    out = outputs(s, tput)
    out["attach"] = np.asarray(kpis.pop("attach"))
    out["kpis"] = reference.summarize(kpis, sample.rc.tti_s)
    return out


def _drop(sample: Sample, dtype) -> np.ndarray:
    """The reference's UE field(s) drawn from the seed's drop key(s)."""
    n = sample.U0.shape[-2]
    fn = lambda k: reference.drop(k, n, sample.rc.extent_m, sample.h_ut_m,
                                  dtype)
    keys = sample.drop_keys
    return np.asarray(jax.vmap(fn)(keys) if keys.ndim == 2 else fn(keys))


def numbers(sample: Sample, names, control: bool = False) -> dict:
    """The compared numbers named in ``names`` (the cell's limits)."""
    ref = _reference(sample, jnp.float32)
    other = _reference(sample, jnp.bfloat16) if control else sample.prog
    other_U0 = _drop(sample, jnp.bfloat16) if control else sample.U0
    tti_axis = 1 if sample.batched else 0
    have = {
        "tput_mismatch": lambda: compare.tput_mismatch(other["tput"],
                                                       ref["tput"]),
        "cell_mismatch": lambda: compare.cell_mismatch(
            other["tput"], ref["tput"], ref["attach"], sample.rc.n_cells,
            sample.batched),
        "net_bits_err": lambda: compare.net_bits_err(other["tput"],
                                                     ref["tput"], tti_axis),
        "state_mismatch": lambda: compare.state_mismatch(other, ref),
        "pos_err_m": lambda: compare.pos_err_m(other["U"], ref["U"]),
        "drop_err_m": lambda: compare.pos_err_m(
            other_U0, _drop(sample, jnp.float32)),
        "kpi_err": lambda: compare.kpi_err(other["kpis"], ref["kpis"]),
        "active_err": lambda: abs(other["kpis"]["mean_active_ues"]
                                  - ref["kpis"]["mean_active_ues"]),
    }
    return {n: float(have[n]()) for n in names}
