"""The program's own names: engine stage scopes, ``crrm:`` host spans, and
their readers (``bench/lib/stages.py``, the ``twin_*`` span metrics)."""
import sys
import time
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.lib import stages, trace  # noqa: E402
from bench.lib.harness import RunRecord, load_module  # noqa: E402

DATA = Path(__file__).parent / "data" / "scoped_trace.json"

BASE = dict(n_ues=64, n_cells=7, n_sectors=1, seed=3,
            pathloss_model_name="UMa", power_W=10.0,
            traffic_model="poisson",
            traffic_params=dict(arrival_rate_hz=300.0,
                                packet_size_bits=12_000.0))
FADED = dict(rayleigh_fading=True, n_rb_subbands=4, harq_bler=0.1,
             attach_ignores_fading=False)
RADIO_ROWS = {"radio/gather", "radio/kernel", "radio/scatter"}


def compiled_stages(text: str) -> set:
    return {stages.stage_of(o)
            for o in stages.hlo_op_names(text).values()} - {stages.UNSCOPED}


def rollout_text(params: dict, **fns_kw) -> str:
    from repro.core.crrm import CRRM
    from repro.core.params import CRRM_parameters
    sim = CRRM(CRRM_parameters(**params))
    fns = sim.episode_fns(telemetry=True, **fns_kw)
    state = sim.init_episode_state(jax.random.PRNGKey(0))
    return fns.rollout.lower(sim.episode_static(), state, 3).compile(
    ).as_text()


def twin_server(ckpt_dir, faults=None, **params):
    from repro.core.crrm import CRRM
    from repro.core.params import CRRM_parameters
    from repro.robust.watchdog import WatchdogConfig
    from repro.sim.mobility import ChurnConfig
    from repro.twin import TwinServer
    sim = CRRM(CRRM_parameters(**{**BASE, **FADED, **params}))
    return TwinServer(sim, ChurnConfig(arrival_rate_hz=400.0,
                                       mean_lifetime_s=0.15,
                                       max_arrivals_per_tti=4),
                      chunk_tti=5, ckpt_dir=str(ckpt_dir),
                      per_tti_fading=True, faults=faults,
                      key=jax.random.PRNGKey(1),
                      watchdog=WatchdogConfig(ckpt_every_chunks=2))


# -- (a) every stage its path runs is a scope of the compiled program ------

PATHS = {
    "incremental_mobility": (
        dict(BASE, mobility_step_m=1.0, mobility_move_frac=0.25),
        dict(radio_mode="incremental"),
        {"call_setup", "mobility", "traffic", "sched", "telemetry"}
        | RADIO_ROWS),
    "incremental_handover": (
        dict(BASE, mobility_step_m=1.0, mobility_move_frac=0.25,
             ho_enabled=True),
        dict(radio_mode="incremental"),
        {"call_setup", "mobility", "attach", "link", "traffic", "sched",
         "telemetry"} | RADIO_ROWS),
    "dense_fading_harq": (
        dict(BASE, **FADED), dict(per_tti_fading=True),
        {"call_setup", "radio", "attach", "link", "traffic", "sched",
         "harq", "telemetry"}),
}


@pytest.mark.parametrize("path", sorted(PATHS))
def test_compiled_rollout_carries_its_stage_scopes(path):
    params, fns_kw, expect = PATHS[path]
    found = compiled_stages(rollout_text(params, **fns_kw))
    assert expect <= found, sorted(expect - found)


def test_compiled_twin_chunk_carries_churn_and_fault_scopes(tmp_path):
    from repro.sim.faults import FaultConfig
    srv = twin_server(tmp_path, faults=FaultConfig(outage_rate_hz=2.0))
    text = srv._chunk.lower(srv.static, srv.state, srv.power,
                            srv.fairness).compile().as_text()
    # under churn nothing is hoisted: the chunk has no call_setup
    expect = {"churn", "faults", "radio", "attach", "link", "traffic",
              "sched", "harq", "telemetry"}
    found = compiled_stages(text)
    assert expect <= found, sorted(expect - found)


# -- (b) the twin's spans in a profiler trace ------------------------------

def test_twin_spans_nest_in_a_trace(tmp_path):
    srv = twin_server(tmp_path / "ckpt")
    srv.step_chunk()                       # compile outside the trace
    jax.profiler.start_trace(str(tmp_path / "trace"))
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench:step_chunk"):
            srv.step_chunk()
    jax.profiler.stop_trace()
    pb = next((tmp_path / "trace").rglob("*.xplane.pb"))

    tr = trace.load_xplane(pb)
    prog = [h for h in tr.host if h.name.startswith("crrm:twin.")]
    chunk = [h for h in prog if h.name == "crrm:twin.chunk"]
    assert len(tr.spans) == 3 and len(chunk) == 3
    for outer, c in zip(tr.spans, sorted(chunk, key=lambda e: e.start)):
        assert outer.start <= c.start and c.end <= outer.end
    children = [h for h in prog if h.name != "crrm:twin.chunk"]
    assert {h.name for h in children} == {
        "crrm:twin.dispatch", "crrm:twin.wait", "crrm:twin.summary",
        "crrm:twin.guard", "crrm:twin.checkpoint"}
    for h in children:
        assert any(c.start <= h.start and h.end <= c.end for c in chunk), h

    found = stages.chunks(stages.load_xplane(pb).program, "twin.chunk")
    assert len(found) == 3
    for c in found:
        assert c.own_ns <= 0.05 * c.ns, c
        assert {"twin.dispatch", "twin.wait", "twin.summary",
                "twin.guard"} <= set(c.parts)
    readbacks = {c.args["readbacks"] for c in found}
    # the summary's telemetry leaves, t, the live-UE count, the guard
    assert readbacks == {srv_leaves(srv) + 3}


def srv_leaves(srv) -> int:
    return len(jax.tree_util.tree_leaves(srv.last_telem))


# -- (c) the readers on a small recorded trace -----------------------------

def test_stage_times_on_a_recorded_trace():
    tr, op_names = stages.load_json(DATA)
    own = stages.stage_ns(tr, op_names)
    # the while loop's own time and an op with no op_name are unscoped;
    # ".../radio/gather" is a gather primitive of radio, not the rows'
    # gather; the op after the last span is outside the window
    assert own == {"call_setup": 100, "sched": 300, "radio/gather": 50,
                   "radio/kernel": 200, "radio/scatter": 40, "radio": 60,
                   "mobility": 150, "unscoped": 100 + 50}
    busy = sum(trace.busy_in_spans(trace.reduce_trace(trace.Trace(
        device=tr.device, spans=tr.spans, host=[]))))
    assert sum(own.values()) == busy
    assert stages.stage_ns(tr, {}) == {"unscoped": busy}


def test_op_names_from_compiled_text():
    f = jax.jit(lambda x: jax.lax.scan(
        lambda c, _: (named(c), None), x, None, length=2)[0])
    text = f.lower(jax.numpy.ones((8,))).compile().as_text()
    found = {stages.stage_of(o) for o in stages.hlo_op_names(text).values()}
    assert {"sched", "radio/kernel"} <= found


def named(c):
    with jax.named_scope("sched"):
        c = jax.numpy.sin(c) * 2.0
    with jax.named_scope("radio"), jax.named_scope("kernel"):
        return jax.numpy.cos(c) + c.sum()


@pytest.mark.parametrize("op_name,stage", [
    ("jit(rollout)/while/body/closed_call/sched/reduce_max", "sched"),
    ("jit(f)/vmap()/while/body/radio/gather/gather", "radio/gather"),
    ("jit(f)/while/body/radio/gather", "radio"),
    ("jit(f)/while/body/radio/kernel/jit(fused)/custom_call",
     "radio/kernel"),
    ("jit(f)/call_setup/radio/mul", "call_setup"),
    ("jit(f)/while/body/add", stages.UNSCOPED),
    (None, stages.UNSCOPED),
])
def test_stage_of_an_op_name(op_name, stage):
    assert stages.stage_of(op_name) == stage


def test_chunk_parts_on_a_recorded_trace():
    tr, _ = stages.load_json(DATA)
    found = stages.chunks(tr.program, "twin.chunk")
    assert [c.ns for c in found] == [1000, 800]
    assert found[0].parts == {"twin.dispatch": 100, "twin.wait": 500,
                              "twin.summary": 200, "twin.guard": 50,
                              "twin.checkpoint": 100}
    assert found[0].args == {"readbacks": 13}
    assert found[0].own_ns == 1000 - 950
    assert found[1].args == {"readbacks": 13}
    assert found[1].own_ns == 800 - 100 - 500 - 150 - 40


# -- the span metrics read the program's in-memory record ------------------

def test_twin_span_metrics_read_the_window():
    from repro.obs.profile import annotate
    with annotate("twin.chunk"):            # before the window: left out
        with annotate("twin.summary", readbacks=99):
            pass
    a = time.perf_counter()
    for k in range(4):
        with annotate("twin.chunk"):
            with annotate("twin.dispatch"):
                time.sleep(0.001)
            with annotate("twin.summary", readbacks=12):
                time.sleep(0.002)
            with annotate("twin.guard", readbacks=1):
                pass
            if k == 3:
                with annotate("twin.checkpoint"):
                    time.sleep(0.004)
    run = RunRecord(spans=[(a, time.perf_counter(), 200)], setup_s=0.0,
                    peak_bytes=0, device_kind="cpu", work={}, red=None)

    def read(name):
        return load_module(ROOT / "bench" / "metrics" / f"{name}.py"
                           ).read(run)

    assert read("twin_readbacks_per_chunk") == 13.0
    assert 1.0 <= read("twin_dispatch_ms_per_chunk") < 50.0
    assert 2.0 <= read("twin_summary_ms_per_chunk") < 50.0
    assert read("twin_wait_ms_per_chunk") == 0.0
    assert 4.0 <= read("twin_ckpt_ms") < 50.0
    assert 0.0 <= read("twin_unspanned_ms_per_chunk") < 1.0
    assert read("twin_guard_ms_per_chunk") < 1.0


def test_span_metrics_read_nothing_without_spans():
    run = RunRecord(spans=[(0.0, 1e-9, 1)], setup_s=0.0, peak_bytes=0,
                    device_kind="cpu", work={}, red=None)
    for name in ("twin_dispatch_ms_per_chunk", "twin_ckpt_ms",
                 "twin_readbacks_per_chunk", "twin_unspanned_ms_per_chunk"):
        assert load_module(ROOT / "bench" / "metrics" / f"{name}.py"
                           ).read(run) is None
