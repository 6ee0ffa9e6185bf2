"""The field built straight onto a UE mesh (``CRRM(params, mesh=mesh)``):
the sharded build against the single-device one, the benchmark's
UE-sharded cell (``uma_mmtc_b.mesh4``) on four shards against the plain
reference, the engine's dirty-row budget counter, and the fused kernel
under ``shard_map``.  At a small size of the cell's configuration (all 57
cells), in one subprocess with four host devices (the device count must
be forced before jax initialises)."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, sys, time
sys.path[:0] = [".", "src"]
import jax, numpy as np
from jax.sharding import Mesh
from repro.core.crrm import CRRM
from repro.core.params import CRRM_parameters
from repro.mac import engine

def emit(name, **kw):
    print("RESULT " + json.dumps(dict(kw, name=name)), flush=True)

cfg = json.load(open("bench/configs/uma_mmtc_b.json"))["CRRM_parameters"]
cfg.update(n_ues=256, traffic_model="full_buffer", seed=11)
mesh = Mesh(np.asarray(jax.devices()[:4]), ("ue",))
one = CRRM(CRRM_parameters(**cfg))
shd = CRRM(CRRM_parameters(**cfg), mesh=mesh)
s1, s2 = one.episode_static(), shd.episode_static()
key = jax.random.PRNGKey(5)
st1, st2 = one.init_episode_state(key), shd.init_episode_state(key)
same = {k: bool(np.array_equal(np.asarray(getattr(s1, k)),
                               np.asarray(getattr(s2, k))))
        for k in ("se", "cqi", "a")}
same["U"] = bool(np.array_equal(np.asarray(st1.U), np.asarray(st2.U)))
p1, p2 = np.asarray(st1.pf_avg), np.asarray(st2.pf_avg)
emit("build", same=same, pf_rel=float(np.abs(p2 - p1).max() / np.abs(p1).max()),
     fad=s2.fad is None,
     shards=[len(x.sharding.device_set) for x in (st2.U, s2.se, st2.pf_avg)])

# what the sharded build refuses: a fading tensor, an uneven split
refused = {}
for name, over in (("fading", dict(rayleigh_fading=True)),
                   ("uneven", dict(n_ues=258))):
    try:
        CRRM(CRRM_parameters(**dict(cfg, **over)), mesh=mesh)
        refused[name] = None
    except ValueError as e:
        refused[name] = str(e)
emit("refused", **refused)

# a mesh-built simulator runs its episodes on its own mesh and axes only
fns_refused = {}
two = Mesh(np.asarray(jax.devices()[:2]), ("ue",))
for name, over in (("same_mesh", dict(mesh=mesh)),
                   ("same_axes", dict(ue_axis="ue")),
                   ("other_mesh", dict(mesh=two)),
                   ("other_axes", dict(ue_axis=("data",))),
                   ("cell_axis", dict(cell_axis="ue"))):
    try:
        shd.episode_fns(radio_mode="incremental", **over)
        fns_refused[name] = None
    except ValueError as e:
        fns_refused[name] = str(e)
emit("fns_refused", **fns_refused)

# the dirty-row budget: shards x min(n_move, n_loc), with the window
# inside one shard's block (frac 0.2: 51 < 64) and covering it (0.5)
kw = dict(radio_mode="incremental", mobility_step_m=0.5)
for frac in (0.2, 0.5):
    before = len(engine.row_budgets())
    fns = shd.episode_fns(mobility_move_frac=frac, **kw)
    fns.rollout.lower(s2, st2, 2)
    emit("budget", frac=frac, got=engine.row_budgets()[before:])

# the fused kernel (interpret mode) under shard_map, and the dense chain
# on the unfaded static (fad None), against the XLA rows
outs = {}
for mode, backend in (("incremental", "xla"), ("incremental", "pallas"),
                      ("dense", None)):
    fns = shd.episode_fns(mobility_move_frac=0.2, inc_backend=backend,
                          **dict(kw, radio_mode=mode))
    s, t = fns.rollout(s2, st2, 4)
    outs[backend] = (np.asarray(t), np.asarray(s.serving))
ref = outs.pop("xla")
for backend, (t, serving) in outs.items():
    emit("vs_xla_rows", backend=backend,
         tput_rel=float(np.abs(t - ref[0]).max() / np.abs(ref[0]).max()),
         serving=bool(np.array_equal(serving, ref[1])))

# the mover window by slices against its index form (tests/window_oracle),
# bitwise, with the window inside each block's slab (0.2: 51 rows of 64)
# and covering blocks (0.5), through the XLA rows and the fused kernel
sys.path.insert(0, "tests")
from window_oracle import index_path
from repro.sim import radio
sl, orc = (CRRM(CRRM_parameters(**cfg), mesh=mesh) for _ in range(2))
for frac in (0.2, 0.5):
    for backend in ("xla", "pallas"):
        fkw = dict(kw, mobility_move_frac=frac, inc_backend=backend)
        before = radio.window_lowerings()["slices"]
        s, t = sl.episode_fns(**fkw).rollout(
            sl.episode_static(), sl.init_episode_state(key), 4)
        slices = radio.window_lowerings()["slices"] - before
        with index_path():
            so, to = orc.episode_fns(**fkw).rollout(
                orc.episode_static(), orc.init_episode_state(key), 4)
        emit("window", frac=frac, backend=backend, slices=slices,
             tput=bool(np.array_equal(np.asarray(t), np.asarray(to))),
             state=[f for f, x, y in zip(s._fields, s, so)
                    if not np.array_equal(np.asarray(x), np.asarray(y))])

# the benchmark's cell on four shards, checked against the reference;
# after each timed call, whether the kept state shares a buffer with the
# running one (the device's bytes would then depend on the draw)
from bench.lib.harness import run

def ptrs(tree):
    return {s.data.unsafe_buffer_pointer()
            for x in jax.tree_util.tree_leaves(tree)
            for s in x.addressable_shards}

shared = []
def watch(driver):
    timed = driver.call
    def call():
        n = timed()
        shared.append(bool(ptrs(driver.kept) & ptrs(driver.state)))
        return n
    driver.call = call

rc = run("uma_mmtc_b.mesh4", 2**33 + 29, 0.3, False,
         t_start=time.perf_counter(), require_chip=False,
         shrink={"params": {"n_ues": 256}}, wrap=watch)
emit("cell", rc=rc, calls=len(shared), shared=any(shared))
"""


@pytest.fixture(scope="module")
def results():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _SCRIPT], capture_output=True,
                         text=True, timeout=300, cwd=ROOT, env=env)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    found = {}
    for line in out.stdout.splitlines():
        if line.startswith("RESULT "):
            r = json.loads(line[len("RESULT "):])
            found.setdefault(r.pop("name"), []).append(r)
        elif line.startswith("{"):
            found["line"] = json.loads(line)
    return found


def test_sharded_build_equals_single_device_build(results):
    """Positions and the serving chain bitwise; the PF seed to 1e-5 (the
    psum reorders the per-cell weight sum); every per-UE leaf on all four
    shards and no fading tensor."""
    (r,) = results["build"]
    assert r["same"] == {"se": True, "cqi": True, "a": True, "U": True}
    assert r["pf_rel"] <= 1e-5
    assert r["fad"] is True
    assert r["shards"] == [4, 4, 4]


def test_mesh_cell_is_correct_against_the_reference(results):
    """The four-shard rollout of ``uma_mmtc_b.mesh4`` passes the cell's
    own check against ``bench/lib/reference.py``."""
    (cell,) = results["cell"]
    assert cell["rc"] == 0
    line = results["line"]
    assert line["correct"] is True, line["check"]
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert line["device"]["count"] == 4


def test_mesh_cell_keeps_its_sample_apart_from_the_state(results):
    """The sampled call keeps only its starting state, which shares no
    buffer with the running state after any timed call: from the second
    call on the device holds one state more whichever call the seed
    draws."""
    (cell,) = results["cell"]
    assert cell["calls"] >= 2
    assert cell["shared"] is False


def test_sharded_build_refuses_fading_and_uneven_splits(results):
    (r,) = results["refused"]
    assert "unfaded channel only" in r["fading"]
    assert "divide evenly" in r["uneven"]


def test_mesh_built_sim_runs_on_its_own_mesh_only(results):
    """``episode_fns`` of a mesh-built simulator takes the build's mesh
    and axes by default or as given, and refuses another mesh, other
    axes or a cell axis."""
    (r,) = results["fns_refused"]
    assert r["same_mesh"] is None and r["same_axes"] is None
    for name in ("other_mesh", "other_axes", "cell_axis"):
        assert "built on a mesh" in r[name], name


def test_row_budget_counter_reads_shards_times_window(results):
    """256 UEs on 4 shards (64 rows each): 51 movers fit one shard's
    block, 128 cover it."""
    got = {r["frac"]: r["got"] for r in results["budget"]}
    assert got == {0.2: [[4, 51]], 0.5: [[4, 64]]}


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("frac", [0.2, 0.5])
def test_mesh_window_rollout_matches_index_path(results, frac, backend):
    """On four shards the rollout takes the mover window by slices and
    ends bitwise where the index path ends: outputs and every state
    leaf."""
    (r,) = [r for r in results["window"]
            if r["frac"] == frac and r["backend"] == backend]
    assert r["slices"] >= 1
    assert r["tput"] is True
    assert r["state"] == []


@pytest.mark.parametrize("backend", ["pallas", None],
                         ids=["fused_kernel", "dense_chain"])
def test_mesh_rollout_paths_match_xla_rows(results, backend):
    """The fused kernel (interpret mode) inside ``shard_map``, and the
    dense chain on the mesh-built static (no fading tensor), against the
    XLA dirty rows."""
    (r,) = [r for r in results["vs_xla_rows"] if r["backend"] == backend]
    assert r["tput_rel"] <= 1e-5
    assert r["serving"] is True
