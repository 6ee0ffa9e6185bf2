"""Tiny sizes of the benchmark's cells for runs on the CPU."""
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

#: per cell: overrides of its parameters and driver arguments
SHRINK = {
    "uma_mmtc.movers20": {"params": {"n_ues": 2000}},
    "uma_embb.twin": {"params": {"n_ues": 120},
                      "driver_args": {"warm_chunks": 10, "chunk_tti": 10}},
    "uma_embb.drops128": {"params": {"n_ues": 60},
                          "driver_args": {"n_drops": 4, "chunk_tti": 10}},
}


def cells():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    return [w["name"] for w in bench["workloads"]]


def shrink(cell):
    """A cell added later without an entry here runs at 64 UEs."""
    return SHRINK.get(cell, {"params": {"n_ues": 64}})
