"""The UE-sharded cell's per-layer metrics on a small two-chip trace."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.lib import trace  # noqa: E402
from bench.lib.harness import RunRecord, load_module  # noqa: E402

METRICS = ROOT / "bench" / "metrics"
E = trace.Event


def _metric(name):
    return load_module(METRICS / f"{name}.py")


def _run(work=None, traced=True):
    """Two calls of 10 TTIs on two chips; chip 1 works half as long."""
    tr = trace.Trace(
        device={
            0: [E("fusion.1", 100, 300), E("fused_sinr.3", 300, 700),
                E("all-reduce-start.2", 700, 720),
                E("all-reduce-done.2", 720, 800),
                E("all-gather.1", 900, 1000), E("fusion.all-reduce", 1000,
                                                1100)],
            1: [E("fused_sinr.3", 300, 500), E("all-reduce.7", 500, 700)],
        },
        spans=[E("rollout", 50, 550), E("rollout", 600, 2050)], host=[])
    w = {"rows": 1000, "shards": 2, "row_budget": 800, "cells": 57,
         "chunks": 1, "sectors": 3, "kernel": "fused_sinr",
         "collectives": ["all-reduce-start.2", "all-reduce-done.2",
                         "all-gather.1", "all-reduce.7"]}
    w.update(work or {})
    return RunRecord(spans=[(0.0, 1.0, 10), (1.0, 2.0, 10)], setup_s=1.0,
                     peak_bytes=0, device_kind="TPU v5 lite", work=w,
                     red=trace.reduce_trace(tr) if traced else None)


def test_idle_and_busy_are_means_over_the_chips():
    run = _run()
    # window 50..2050; chip 0 busy 100..800 and 900..1100 = 900 ns,
    # chip 1 300..700 = 400 ns
    assert _metric("device_idle_pct.mesh").read(run) == pytest.approx(
        100.0 * (1.0 - 650.0 / 2000.0))
    # inside the spans: chip 0 450 + 400 ns, chip 1 250 + 100 ns, over
    # 20 TTIs
    assert _metric("engine_device_ms_per_tti.mesh").read(run) == \
        pytest.approx((850 + 350) / 2 / 1e6 / 20)
    # the kernel: chip 0 400 ns, chip 1 200 ns
    assert _metric("fused_sinr_ms_per_tti.mesh").read(run) == \
        pytest.approx((400 + 200) / 2 / 1e6 / 20)


def test_collectives_are_found_by_hlo_name():
    m = _metric("mesh_collective_ms_per_tti")
    # chip 0: 20 + 80 + 100 ns, chip 1: 200 ns; the fusion is not listed
    assert m.read(_run()) == pytest.approx((200 + 200) / 2 / 1e6 / 20)


#: lines of a compiled mesh rollout (XLA names an all-reduce after the
#: JAX primitive), with a collective inside a fusion and an async pair
_HLO = """\
%fused_computation.79 (param_0: f32[57,1]) -> f32[57,1] {
  %param_0 = f32[57,1]{0,1} parameter(0)
  ROOT %psum.4 = f32[57,1]{0,1} all-reduce(%param_0), channel_id=2, to_apply=%add
}

%body (p: (s32[], f32[57,1])) -> (s32[], f32[57,1]) {
  %pmax.30 = f32[57,1]{0,1:T(1,128)S(1)} all-reduce(%bitcast.514), channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%region_8.10, metadata={op_name="jit(rollout)/shard_map/while/body/closed_call/sched/pmax"}
  %psum.9 = f32[57,1]{0,1:T(1,128)S(1)} all-reduce(%bitcast.513), channel_id=1, to_apply=%region_10.12, metadata={op_name="jit(rollout)/shard_map/while/body/closed_call/sched/psum"}
  %fusion.12 = f32[57,1]{0,1} fusion(%psum.9), kind=kLoop, calls=%fused_computation.79, metadata={op_name="jit(rollout)/x"}
  %get-tuple-element.1545 = s32[]{:T(128)} get-tuple-element(%all-reduce.20), index=0
  %all-gather-start.3 = (f32[8], f32[32]) all-gather-start(%x), dimensions={0}
  ROOT %all-gather-done.3 = f32[32] all-gather-done(%all-gather-start.3)
}

ENTRY %main.19_spmd (param.22: f32[57,3]) -> f32[57,3] {
  %all-reduce.20 = (s32[]{:T(128)}, s32[]{:T(128)}) all-reduce(%while.27, %while.28), channel_id=1, to_apply=%region_12.15
  ROOT %pmax.25 = u32[2]{0:T(128)} all-reduce(%get-tuple-element.1553), channel_id=1, to_apply=%region_13.16
}
"""


def test_collective_ops_are_found_by_opcode():
    """Every instruction whose opcode is a collective, whatever its name;
    one inside a fusion under the fusion's name, which the trace shows;
    operands and metadata that mention a collective are not one."""
    m = _metric("mesh_collective_ms_per_tti")
    assert m.collective_ops(_HLO) == [
        "all-gather-done.3", "all-gather-start.3", "all-reduce.20",
        "fusion.12", "pmax.25", "pmax.30", "psum.9"]


def test_roofline_counts_the_moved_rows_per_chip():
    roof = load_module(METRICS / "fused_sinr_roofline_pct.py")
    run = _run()
    ops, nbytes = roof.work(500, 57, 1, 3)
    want = roof.share_pct(ops * 20, nbytes * 20, 300e-9, "TPU v5 lite")
    assert _metric("mesh_fused_sinr_roofline_pct").read(run) == \
        pytest.approx(want)


def test_rows_per_moved_row_reads_the_engine_counter():
    m = _metric("mesh_rows_per_moved_row")
    assert m.read(_run()) == pytest.approx(2 * 800 / 1000)
    assert m.read(_run(traced=False)) == pytest.approx(1.6)


def test_mesh_metrics_find_nothing_without_a_trace_or_a_counter():
    bare = _run(traced=False)
    for name in ("device_idle_pct.mesh", "engine_device_ms_per_tti.mesh",
                 "fused_sinr_ms_per_tti.mesh", "mesh_collective_ms_per_tti",
                 "mesh_fused_sinr_roofline_pct"):
        assert _metric(name).read(bare) is None, name
    w = _run().work
    no_counter = _run()._replace(work={k: v for k, v in w.items()
                                       if k not in ("shards", "row_budget")})
    assert _metric("mesh_rows_per_moved_row").read(no_counter) is None
    assert _metric("mesh_fused_sinr_roofline_pct").read(no_counter) is None
