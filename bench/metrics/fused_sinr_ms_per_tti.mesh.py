"""``fused_sinr_ms_per_tti`` on the UE-sharded cell: the kernel's own
time per simulated TTI on a chip, mean over the chips (the trace's
operation times already are)."""
from bench.metrics.fused_sinr_ms_per_tti import read  # noqa: F401
