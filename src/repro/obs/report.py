"""Compiled-step reports: cost analysis + roofline over the TTI engine.

The seed repo shipped ``analysis/hlo.py`` (collective wire bytes from HLO
text) and ``analysis/roofline.py`` (the three-term roofline) pointed at
nothing.  This module points them at the thing that matters: the compiled
episode rollout.  ``jax``'s AOT path gives everything without running a
single TTI:

    lowered  = fns.rollout.lower(static, state, n_tti)
    compiled = lowered.compile()
    compiled.cost_analysis()     # XLA FLOPs + bytes accessed
    compiled.as_text()           # post-SPMD HLO -> collective wire bytes

:func:`episode_report` wraps that for one simulator configuration and
returns the artifact dict :mod:`repro.analysis.roofline` consumes
(``n_devices`` / ``hlo_flops`` / ``hlo_bytes`` / ``collective_wire_bytes``
/ ``model_flops``), plus the raw collective counts.  ``model_flops`` is
the *useful* Figure-1 physics estimate (:func:`model_flops_episode`), so
the roofline's useful/HLO column reads as "how much compiled compute is
radio math vs overhead".

Run as a module to write per-scenario JSON artifacts and the markdown
roofline table CI uploads:

    PYTHONPATH=src python -m repro.obs.report --scenario dense_urban \
        --n-tti 20 --out artifacts/obs
"""
from __future__ import annotations

import argparse
import json
import os
from typing import Optional

import jax

from repro.analysis import hlo, roofline


def model_flops_episode(n_ues: int, n_cells: int, n_freq: int,
                        n_tti: int) -> float:
    """Useful-physics FLOPs of ``n_tti`` dense radio TTIs (an estimate).

    Per (UE, cell) link and TTI the Figure-1 chain costs roughly:
    geometry + pathloss + antenna ~ 40 flops, then RSRP/interference/SINR
    ~ 6 per frequency chunk; the MAC adds ~ 10 per (UE, chunk).  The
    point is a stable order-of-magnitude yardstick for the roofline's
    useful/HLO ratio, not an exact count (XLA's own number IS the exact
    executed count; dividing by this shows overhead factors).
    """
    radio = n_ues * n_cells * (40.0 + 6.0 * n_freq)
    mac = 10.0 * n_ues * n_freq
    return float(n_tti) * (radio + mac)


def _cost_dict(compiled) -> dict:
    """Normalise ``compiled.cost_analysis()`` across jax versions."""
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return dict(cost or {})


def compiled_cost(compiled, n_devices: int = 1) -> dict:
    """FLOPs / HBM bytes / collective wire bytes of one executable.

    ``compiled`` is a ``jax.stages.Compiled`` (``fn.lower(...).compile()``).
    Wire bytes come from :func:`repro.analysis.hlo.collective_stats` over
    the post-partitioning HLO text -- including the trip-count correction
    for collectives inside the scan body, which XLA's cost analysis counts
    once.

    Caveat: that same limitation applies to ``hlo_flops``/``hlo_bytes`` --
    XLA counts a while/scan body ONCE, not times its trip count, so an
    episode's numbers are closer to "per-TTI program cost" than "episode
    cost".  Compare artifacts at equal ``n_tti``.
    """
    cost = _cost_dict(compiled)
    stats = hlo.collective_stats(compiled.as_text(),
                                 default_group=max(n_devices, 1))
    return {
        "hlo_flops": float(cost.get("flops", 0.0)),
        "hlo_bytes": float(cost.get("bytes accessed", 0.0)),
        "collective_wire_bytes": stats.total_wire_bytes,
        "collective_counts": dict(stats.counts),
    }


def episode_report(sim, n_tti: int, *, mesh=None, scenario: str = "",
                   telemetry: bool = False, action=None) -> dict:
    """Cost-analyse the compiled episode rollout of one simulator.

    AOT only -- lowers and compiles ``episode_fns().rollout`` for this
    configuration without executing it, then reads XLA's cost analysis
    and the HLO collective traffic.  Returns a roofline-ready artifact
    dict (see :func:`repro.analysis.roofline.from_artifact`); on a
    backend whose cost analysis is unavailable the artifact carries
    ``skipped`` + ``reason`` instead (the roofline table renders those
    as skipped rows).
    """
    fns = sim.episode_fns(mesh=mesh)
    static = sim.episode_static()
    state = sim.init_episode_state()
    n_dev = 1
    if mesh is not None:
        n_dev = int(mesh.devices.size)
    art = {
        "scenario": scenario, "n_ues": sim.n_ues, "n_cells": sim.n_cells,
        "n_tti": int(n_tti), "n_devices": n_dev,
        "model_flops": model_flops_episode(
            sim.n_ues, sim.n_cells, sim.params.n_freq, n_tti),
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
    }
    try:
        args = (static, state, n_tti) if action is None else \
            (static, state, n_tti, action)
        compiled = fns.rollout.lower(*args).compile()
        art.update(compiled_cost(compiled, n_dev))
    except Exception as e:          # pragma: no cover - backend dependent
        art.update(skipped=True, reason=f"{type(e).__name__}: {e}")
        return art
    if not art["hlo_flops"] and not art["hlo_bytes"]:
        art.update(skipped=True,
                   reason="cost analysis returned no flops/bytes")
    return art


def roofline_table(artifacts: dict) -> str:
    """Markdown roofline table over ``{name: artifact}`` dicts.

    An artifact compiled for a device with no published peaks
    (``roofline.PEAKS``, e.g. the CPU) keeps its counts in the JSON and
    renders a row that says so, not times from another device's peaks.
    """
    lines = ["| cell | compute ms | memory ms | collective ms | dominant "
             "| useful/HLO | roofline frac |",
             "|---|---|---|---|---|---|---|"]
    for name in sorted(artifacts):
        art = artifacts[name]
        if art.get("skipped"):
            lines.append(f"| {name} | - | - | - | skipped: "
                         f"{art.get('reason', '')[:40]} | - | - |")
        elif art["device_kind"] not in roofline.PEAKS:
            lines.append(f"| {name} | - | - | - | no peaks for "
                         f"{art['device_kind']!r} | - | - |")
        else:
            lines.append(roofline.format_row(name, art))
    return "\n".join(lines)


def write_report(out_dir: str, artifacts: dict) -> str:
    """Write per-name JSON artifacts + ``roofline.md``; returns the table."""
    os.makedirs(out_dir, exist_ok=True)
    for name, art in artifacts.items():
        with open(os.path.join(out_dir, f"{name}.json"), "w") as f:
            json.dump(art, f, indent=2, sort_keys=True)
            f.write("\n")
    table = roofline_table(artifacts)
    with open(os.path.join(out_dir, "roofline.md"), "w") as f:
        f.write("# Compiled TTI-step roofline\n\n" + table + "\n")
    return table


def main(argv: Optional[list] = None) -> None:
    from repro.compile_cache import enable_compile_cache
    from repro.core.crrm import CRRM
    from repro.sim.scenarios import make_scenario, scenario_names

    ap = argparse.ArgumentParser(
        description="cost-analyse the compiled episode step per scenario")
    ap.add_argument("--scenario", action="append", default=None,
                    help="registry preset (repeatable; default: all)")
    ap.add_argument("--n-tti", type=int, default=20)
    ap.add_argument("--n-ues", type=int, default=None,
                    help="override the preset's UE count (CI shrink)")
    ap.add_argument("--out", default="artifacts/obs")
    args = ap.parse_args(argv)
    enable_compile_cache()
    names = args.scenario or list(scenario_names())
    arts = {}
    for name in names:
        overrides = {} if args.n_ues is None else {"n_ues": args.n_ues}
        sim = CRRM(make_scenario(name, **overrides))
        arts[name] = episode_report(sim, args.n_tti, scenario=name)
    print(write_report(args.out, arts))


if __name__ == "__main__":
    main()
