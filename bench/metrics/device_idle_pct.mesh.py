"""``device_idle_pct.batch`` on the UE-sharded cell: the trace's busy
time is already the mean over the chips (``bench/lib/trace.py``)."""
from bench.lib.harness import BENCH, load_module

read = load_module(BENCH / "metrics" / "device_idle_pct.batch.py").read
