"""The trace reduction on a small recorded trace (bench/tests/data)."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench.lib import trace  # noqa: E402

DATA = Path(__file__).parent / "data" / "small_trace.json"


def test_busy_union_idle_and_per_name_time():
    red = trace.reduce_trace(trace.load_json(DATA))
    assert red.window == (50, 61000)
    # union of [100,300] [400,450] [30000,60000]; the op after the last
    # span lies outside the window
    assert red.busy[0] == [(100, 300), (400, 450), (30000, 60000),
                           (70000, 80000)]
    assert red.busy_ns == 200 + 50 + 30000
    assert red.op_ns == {"fusion.1": 100, "fused_sinr": 150,
                         "fusion.2": 50, "fusion.3": 30000}
    assert trace.busy_in_spans(red) == [250, 30000]


def test_gaps_are_labelled_by_span_and_host_event():
    red = trace.reduce_trace(trace.load_json(DATA))
    assert red.gap_ns == {
        "rollout/between ops": (50 + 100 + 1000, 3),
        "between calls/TransferFromDevice": (29550, 1)}
    idle = (red.window[1] - red.window[0]) - red.busy_ns
    assert sum(ns for ns, _ in red.gap_ns.values()) == idle
    b = trace.breakdown(red)
    assert b["device_ops"][0] == ["fusion.3", 30000 / 1e9]
    assert b["idle_gaps"][0] == ["between calls/TransferFromDevice (1 gaps)",
                                 29550 / 1e9]


def test_interval_helpers():
    assert trace.union([(5, 9), (1, 3), (2, 4), (9, 9)]) == [(1, 4), (5, 9)]
    merged = [(1, 4), (5, 9)]
    assert trace.covered(merged, 0, 10) == 7
    assert trace.covered(merged, 3, 6) == 2
    assert trace.gaps(merged, 0, 10) == [(0, 1), (4, 5), (9, 10)]


def test_op_names_and_kernel_instances():
    hlo = ("%fused_sinr.10 = (f32[8,1]{1,0}) custom-call(f32[8,3]{1,0} "
           "%pad.43), custom_call_target=\"tpu_custom_call\"")
    reader = ("%multiply_reduce_fusion.2 = f32[8]{0} fusion(f32[8,1]{1,0} "
              "%jit_fused_sinr_accumulate_.28)")
    assert trace.op_name(hlo) == "fused_sinr.10"
    assert trace.op_name(reader) == "multiply_reduce_fusion.2"
    assert trace.op_name("fusion.3") == "fusion.3"
    assert trace.is_op("fused_sinr.10", "fused_sinr")
    assert trace.is_op("fused_sinr", "fused_sinr")
    assert not trace.is_op("multiply_reduce_fusion.2", "fused_sinr")
    assert not trace.is_op("jit_fused_sinr_accumulate_.28", "fused_sinr")


def test_nested_operations_count_their_own_time():
    E = trace.Event
    loop = [E("while.9", 0, 1000), E("fusion.1", 100, 300),
            E("fused_sinr.10", 300, 700), E("copy.2", 650, 800),
            E("while.3", 850, 990), E("fusion.4", 900, 950)]
    assert trace.self_times(loop) == [1000 - 200 - 400 - 150 - 140, 200,
                                      400, 150, 140 - 50, 50]
    red = trace.reduce_trace(trace.Trace(
        device={0: loop}, spans=[E("rollout", 0, 1000)], host=[]))
    assert red.busy_ns == 1000
    assert sum(red.op_ns.values()) == 1000
    assert red.op_ns["while.9"] == 110


def test_xplane_spans_and_host_events(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x) * 2.0)
    x = jnp.ones((64,))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(2):
        with jax.profiler.TraceAnnotation("bench:call"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = trace.load_xplane(next(tmp_path.rglob("*.xplane.pb")))
    assert [s.name for s in tr.spans] == ["call", "call"]
    assert all(s.end > s.start for s in tr.spans)
    assert any(h.name.startswith("PjitFunction") for h in tr.host)
