"""CRRM -- the main simulator class (the paper's public API).

Wires the Figure-1 dependency graph, binds the pluggable pathloss strategy,
and exposes the mutation / query API.  Queries trigger the recursive update
phase; mutations trigger the invalidation phase only.

>>> from repro.core.params import CRRM_parameters
>>> from repro.core.crrm import CRRM
>>> sim = CRRM(CRRM_parameters(n_ues=50, pathloss_model_name="UMa", seed=1))
>>> tput = sim.get_UE_throughputs()          # full evaluation
>>> sim.move_UE(3, (100.0, 200.0, 1.5))      # invalidates row 3 only
>>> tput2 = sim.get_UE_throughputs()         # row-local smart update
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as PSpec

from repro.core import blocks
from repro.core.graph import Graph, RootNode
from repro.core.params import CRRM_parameters
from repro.mac import traffic
from repro.obs.profile import annotate
from repro.sim import deploy, radio
from repro.sim.antenna import Antenna_gain, sector_boresights
from repro.sim.pathloss import make_pathloss


def drop_ues(key, n_ues: int, extent_m: float, h_ut_m: float):
    """The UE field of a drop: uniform over the square at UE height."""
    xy = jax.random.uniform(key, (n_ues, 2), minval=0.0, maxval=extent_m)
    return jnp.concatenate([xy, jnp.full((n_ues, 1), h_ut_m)], axis=1)


class CRRM:
    def __init__(self, params: CRRM_parameters, mesh=None):
        """Build the simulator for ``params``.

        ``mesh`` (a ``jax.sharding.Mesh`` every axis of which shards the
        UE dimension) builds the field straight onto the mesh for an
        episode run with ``episode_fns(mesh=mesh)``: each shard draws
        and keeps its own rows, computes their serving chain with
        ``radio.radio_init`` and the PF seed with the per-cell sums
        psummed across shards, and no device ever holds an
        ``(n_ues, n_cells)`` array (DESIGN.md §Million-UE-scaling).
        Such a simulator has no smart-update graph: only
        ``episode_static``, ``init_episode_state`` and ``episode_fns``.
        """
        self.params = params
        self.mesh = mesh
        p = params
        key = jax.random.PRNGKey(p.seed)
        k_ue, k_cell, k_fad = jax.random.split(key, 3)

        # -- topology roots -------------------------------------------------
        if p.ue_positions is not None:
            U0 = jnp.asarray(p.ue_positions, dtype=jnp.float32)
        elif mesh is None:
            U0 = drop_ues(k_ue, p.n_ues, p.extent_m, p.h_ut_m)
        else:
            U0 = None                   # drawn on the mesh, shard by shard
        if p.cell_positions is not None:
            C0 = jnp.asarray(p.cell_positions, dtype=jnp.float32)
        else:
            n_cells = p.n_cells or 7
            n_sites = max(1, n_cells // p.n_sectors)
            rings = 0
            while 1 + 3 * rings * (rings + 1) < n_sites:
                rings += 1
            sites = deploy.hex_sites(rings, isd_m=p.extent_m / (2 * rings + 1)
                                     if rings else p.extent_m, z=p.h_bs_m)
            sites = sites[:n_sites] + jnp.asarray(
                [p.extent_m / 2, p.extent_m / 2, 0.0])
            C0 = deploy.replicate_sectors(sites, p.n_sectors)
        self.n_cells = int(C0.shape[0])
        self.n_ues = p.n_ues if U0 is None else int(U0.shape[0])

        # frequency grid: n_subbands power subbands x n_rb_subbands CQI
        # subbands each; every per-frequency tensor below has trailing axis
        # n_freq (== n_subbands in the legacy wideband configuration).
        self.n_freq = p.n_freq
        if p.power_matrix is not None:
            P0 = jnp.asarray(p.power_matrix, dtype=jnp.float32)
            if p.n_rb_subbands > 1:     # split each subband's power evenly
                P0 = jnp.repeat(P0, p.n_rb_subbands,
                                axis=1) / p.n_rb_subbands
        else:
            P0 = jnp.full((self.n_cells, self.n_freq),
                          p.power_W / self.n_freq, dtype=jnp.float32)

        bore0 = sector_boresights(self.n_cells // p.n_sectors, p.n_sectors)

        # the strategy pattern: model name -> class -> bound pathgain_function
        self.pathloss_model = make_pathloss(p.pathloss_model_name,
                                            **p.pathloss_params)
        self.pathgain_function = self.pathloss_model.get_pathgain
        antenna = Antenna_gain(phi_3dB_deg=p.antenna_phi_3dB_deg,
                               A_max_dB=p.antenna_A_max_dB)
        self.antenna = antenna
        #: the hashable pure-radio configuration (sim.radio) every
        #: consumer -- graph nodes, TTI engine, env resets -- derives from
        self._radio_cfg = radio.config_from_params(
            p, self.pathgain_function, antenna)

        init_backlog, self._traffic_step = traffic.make_traffic(
            p.traffic_model, self.n_ues, p.tti_s, **p.traffic_params)
        if mesh is not None:
            self._sharded = self._build_sharded(k_ue, U0, C0, P0, bore0,
                                                init_backlog)
            return

        if p.rayleigh_fading:
            F0 = radio.draw_fading(self._radio_cfg, k_fad, self.n_ues,
                                   self.n_cells)
        else:
            F0 = radio.unit_fading(self._radio_cfg, self.n_ues, self.n_cells)

        # -- graph ------------------------------------------------------------
        g = Graph(smart=p.smart)
        self.graph = g
        self.U = g.add(RootNode("U", U0))
        self.C = g.add(RootNode("C", C0))
        self.P = g.add(RootNode("P", P0))
        self.boresight = g.add(RootNode("boresight", bore0))
        self.fading = g.add(RootNode("fading", F0))

        self.D = g.add(blocks.DistanceNode(self.U, self.C))
        self.G = g.add(blocks.GainNode(
            self.D, self.U, self.C, self.boresight, self.fading,
            self.pathgain_function, antenna, p.n_sectors))
        self.R = g.add(blocks.RSRPNode(self.G, self.P))
        if p.rayleigh_fading and p.attach_ignores_fading:
            # association on the long-term mean: a parallel unfaded branch
            self.ones = g.add(RootNode(
                "ones", jnp.ones((self.n_ues, self.n_cells))))
            self.G_mean = g.add(blocks.GainNode(
                self.D, self.U, self.C, self.boresight, self.ones,
                self.pathgain_function, antenna, p.n_sectors))
            self.G_mean.name = "G_mean"
            self.R_mean = g.add(blocks.RSRPNode(self.G_mean, self.P))
            self.R_mean.name = "RSRP_mean"
            g.nodes["G_mean"] = g.nodes.pop("G")  # fix registry keys
            g.nodes["G"] = self.G
            g.nodes["RSRP_mean"] = g.nodes.pop("RSRP")
            g.nodes["RSRP"] = self.R
            self.a = g.add(blocks.AttachmentNode(self.R_mean))
        else:
            self.a = g.add(blocks.AttachmentNode(self.R))
        self.w = g.add(blocks.WantedNode(self.R, self.a))
        self.u = g.add(blocks.InterferenceNode(self.R, self.w))
        self.gamma = g.add(blocks.SINRNode(self.w, self.u, p.chunk_noise_W))
        self.cqi = g.add(blocks.CQINode(
            self.gamma, p.n_rb_subbands, p.cqi_report == "wideband",
            p.cqi_eesm_beta))
        self.mcs = g.add(blocks.MCSNode(self.cqi))
        self.se = g.add(blocks.SpectralEfficiencyNode(self.mcs, self.cqi))
        self.shannon = g.add(blocks.ShannonNode(
            self.gamma, p.chunk_bandwidth_Hz, p.n_tx, p.n_rx))
        self.throughput = g.add(blocks.ThroughputNode(
            self.se, self.a, self.n_cells, p.chunk_bandwidth_Hz,
            p.fairness_p))

        # -- MAC subsystem: traffic -> buffers -> scheduler -> served -------
        # The legacy ThroughputNode above is the full_buffer + fairness_p
        # special case of this chain (asserted in tests/test_mac.py).
        self.buffer = g.add(blocks.BufferNode(init_backlog()))
        self.sched = g.add(blocks.ScheduleNode(
            self.se, self.cqi, self.a, self.buffer, self.n_cells,
            p.rb_per_chunk, p.scheduler_policy, p.fairness_p))
        self.served = g.add(blocks.ServedThroughputNode(
            self.sched, self.se, self.buffer,
            p.subband_bandwidth_Hz / p.n_rb, p.tti_s))

    # ---------------------------------------------------------------- mutations
    def move_UE(self, i: int, xyz) -> None:
        self.U.set_rows(np.asarray([i]), np.asarray(xyz, np.float32)[None, :])

    def move_UEs(self, idx, xyz) -> None:
        self.U.set_rows(np.asarray(idx), np.asarray(xyz, np.float32))

    def set_UE_positions(self, U) -> None:
        self.U.set(jnp.asarray(U, dtype=jnp.float32))

    def set_power_matrix(self, P) -> None:
        """Set per-cell/subband powers; accepts the documented
        (n_cells, n_subbands) shape (expanded onto the n_freq grid as in
        the constructor) or an already-expanded (n_cells, n_freq) one."""
        P = jnp.asarray(P, dtype=jnp.float32)
        p = self.params
        if p.n_rb_subbands > 1 and P.shape[1] == p.n_subbands:
            P = jnp.repeat(P, p.n_rb_subbands, axis=1) / p.n_rb_subbands
        if P.shape != (self.n_cells, self.n_freq):
            raise ValueError(
                f"power matrix must be (n_cells, n_subbands)="
                f"({self.n_cells}, {p.n_subbands}) or (n_cells, n_freq)="
                f"({self.n_cells}, {self.n_freq}); got {tuple(P.shape)}")
        self.P.set(P)

    def set_cell_power(self, j: int, k: int, watts: float) -> None:
        """Set cell ``j``'s power on *subband* ``k`` (spread evenly over
        the subband's CQI chunks when ``n_rb_subbands > 1``)."""
        s = self.params.n_rb_subbands
        cols = jnp.arange(k * s, (k + 1) * s)
        self.P.set_at((j, cols), watts / s)

    def resample_fading(self, key) -> None:
        """Redraw the fast-fading root via the ONE documented fading draw
        (``radio.draw_fading``) -- the same stream the episode engine's
        per-TTI redraw and the env's topology resets consume, so equal keys
        give bit-identical fading everywhere."""
        self.fading.set(radio.draw_fading(self._radio_cfg, key, self.n_ues,
                                          self.n_cells))

    def add_traffic(self, idx, bits) -> None:
        """Queue arrival bits onto selected UEs (row-local MAC flood)."""
        self.buffer.add_bits(idx, bits)

    def set_backlog(self, backlog) -> None:
        self.buffer.set(jnp.asarray(backlog, dtype=jnp.float32))

    def step_traffic(self, key, t: int = 0) -> None:
        """Draw one TTI of arrivals from the configured traffic model."""
        arrivals = self._traffic_step(key, t)
        self.buffer.set(self.buffer._data + arrivals)

    # ------------------------------------------------------------------- queries
    def get_distances(self):
        return self.D.update()

    def get_pathgains(self):
        return self.G.update()

    def get_RSRP(self):
        return self.R.update()

    def get_attachment(self):
        return self.a.update()

    def get_SINR(self):
        """(n_ue, n_freq) linear SINR (n_freq == n_subbands unless
        ``n_rb_subbands > 1`` splits the grid into CQI subbands)."""
        return self.gamma.update()

    def get_SINR_dB(self):
        return 10.0 * jnp.log10(jnp.maximum(self.get_SINR(), 1e-12))

    def get_CQI(self):
        return self.cqi.update()

    def get_MCS(self):
        return self.mcs.update()

    def get_spectral_efficiency(self):
        return self.se.update()

    def get_shannon_capacities(self):
        """(n_ue, n_freq) bits/s upper bound."""
        return self.shannon.update()

    def get_UE_throughputs(self):
        """(n_ue,) bits/s: fairness-weighted share summed over subbands."""
        return self.throughput.update().sum(axis=1)

    def get_backlog(self):
        """(n_ue,) bits queued (inf for full-buffer traffic)."""
        return self.buffer.update()

    def get_schedule(self):
        """(n_ue, n_freq) resource blocks granted this TTI
        (``rb_per_chunk`` RBs available per frequency chunk)."""
        return self.sched.update()

    def get_served_throughputs(self):
        """(n_ue,) bits/s through the MAC chain (grant capped by backlog)."""
        return self.served.update().sum(axis=1)

    # ---------------------------------------------------------------- pure radio
    def radio_config(self) -> "radio.RadioConfig":
        """The hashable pure-radio configuration bound to this simulator's
        pathloss/antenna closures (``repro.sim.radio``)."""
        return self._radio_cfg

    def radio_static(self) -> "radio.RadioStatic":
        """The :class:`~repro.sim.radio.RadioStatic` pytree for the current
        graph roots (cell positions, powers, boresights).  Pure data + a
        static config: hand it to ``radio.radio_forward`` to run the whole
        chain for arbitrary UE positions without touching the graph."""
        return radio.RadioStatic(C=self.C._data, P=self.P._data,
                                 bore=self.boresight._data,
                                 cfg=self._radio_cfg)

    # ------------------------------------------------------------------ episodes
    def _build_sharded(self, k_ue, U0, C0, P0, bore0, init_backlog):
        """The episode's inputs built on ``self.mesh``, one span long.

        Positions are the single-device draw (``drop_ues`` of the same
        key) placed row-sharded; each shard runs ``radio.radio_init`` on
        its rows (serving chain only, no fading) and the stationary PF
        seed, whose per-cell maximum and sum cross shards as
        ``pmax``/``psum``.  Returns ``(EpisodeStatic, U, backlog,
        pf_avg)``.
        """
        from repro.mac import engine as mac_engine
        p, mesh, cfg, n_cells = self.params, self.mesh, self._radio_cfg, \
            self.n_cells
        axes = tuple(mesh.axis_names)
        shards = mesh.size
        if p.rayleigh_fading:
            raise ValueError(
                "CRRM(mesh=...) builds the unfaded channel only: a fading "
                "tensor is (n_ues, n_cells) per draw, which the sharded "
                "build exists not to hold; pass rayleigh_fading=False or "
                "build without a mesh")
        if self.n_ues % shards:
            raise ValueError(f"n_ues={self.n_ues} must divide evenly over "
                             f"the {shards} shards of mesh axes {axes}")
        rows = NamedSharding(mesh, PSpec(axes))
        rows2 = NamedSharding(mesh, PSpec(axes, None))
        rep = NamedSharding(mesh, PSpec())

        def chain(U, backlog, C, P, bore):
            rs = radio.radio_init(cfg, U, C, bore, None, P)
            avg = mac_engine.stationary_served_tput(
                p, n_cells, rs.se, rs.cqi, rs.a, backlog, ue_axis=axes)
            return rs.se, rs.cqi, rs.a, avg

        build = jax.jit(jax.shard_map(
            chain, mesh=mesh,
            in_specs=(PSpec(axes, None), PSpec(axes), PSpec(), PSpec(),
                      PSpec()),
            out_specs=(PSpec(axes, None), PSpec(axes, None), PSpec(axes),
                       PSpec(axes)),
            check_vma=False))
        with annotate("build.sharded", shards=shards,
                      rows_per_shard=self.n_ues // shards):
            if U0 is None:
                U0 = jax.jit(drop_ues, static_argnums=(1, 2, 3),
                             out_shardings=rows2)(k_ue, self.n_ues,
                                                  p.extent_m, p.h_ut_m)
            else:
                U0 = jax.device_put(U0, rows2)
            backlog = jax.jit(init_backlog, out_shardings=rows)()
            C0, P0, bore0 = jax.device_put((C0, P0, bore0), rep)
            se, cqi, a, avg = build(U0, backlog, C0, P0, bore0)
            jax.block_until_ready((U0, backlog, se, cqi, a, avg))
        static = mac_engine.EpisodeStatic(se=se, cqi=cqi, a=a, C=C0, P=P0,
                                          bore=bore0, fad=None)
        return static, U0, backlog, avg

    def _sharded_episode_state(self, key):
        """``init_episode_state`` of a mesh-built simulator: every leaf
        placed on the mesh (per-UE rows sharded, scalars replicated)."""
        from repro.mac.engine import EpisodeState
        static, U, backlog, avg = self._sharded
        mesh = self.mesh
        rows = NamedSharding(mesh, PSpec(tuple(mesh.axis_names)))
        rep = NamedSharding(mesh, PSpec())
        zeros = lambda dt: jax.jit(lambda: jnp.zeros((self.n_ues,), dt),
                                   out_shardings=rows)()
        scalar = lambda x: jax.device_put(jnp.asarray(x), rep)
        return EpisodeState(
            U=U, backlog=backlog, pf_avg=avg, rr_cursor=scalar(jnp.int32(0)),
            key=scalar(key), harq_bits=zeros(jnp.float32),
            harq_retx=zeros(jnp.int32), serving=static.a,
            ttt=zeros(jnp.int32), t=scalar(jnp.int32(0)))

    def init_episode_state(self, key=None):
        """Gather the full episode carry as an explicit ``EpisodeState``.

        Everything a MAC episode mutates -- buffers, PF EWMA, round-robin
        cursor, HARQ processes, serving cells / TTT counters, positions and
        the PRNG key -- in one pytree (DESIGN.md §Env-API).  Seeds the PF
        average from the single-shot graph's served throughput (the
        stationary alpha-fair point) and the serving cells from the current
        attachment, unless a previous ``sync_episode_state`` left state on
        the simulator.  ``key=None`` derives the legacy per-sim episode key
        from ``params.seed``.
        """
        from repro.mac.engine import EpisodeState
        if key is None:
            key = radio.episode_key(self.params.seed)
        if self.mesh is not None:
            return self._sharded_episode_state(key)
        n = self.n_ues
        avg0 = getattr(self, "_pf_avg", None)
        if avg0 is None:
            avg0 = self.get_served_throughputs()
        hbits0 = getattr(self, "_harq_bits", None)
        if hbits0 is None:
            hbits0 = jnp.zeros((n,), jnp.float32)
        hretx0 = getattr(self, "_harq_retx", None)
        if hretx0 is None:
            hretx0 = jnp.zeros((n,), jnp.int32)
        a0 = getattr(self, "_ho_serving", None)
        if a0 is None:
            a0 = self.get_attachment()
        ttt0 = getattr(self, "_ho_ttt", None)
        if ttt0 is None:
            ttt0 = jnp.zeros((n,), jnp.int32)
        return EpisodeState(
            U=self.U._data, backlog=self.buffer._data, pf_avg=avg0,
            rr_cursor=jnp.int32(self.sched.cursor), key=key,
            harq_bits=jnp.asarray(hbits0, jnp.float32),
            harq_retx=jnp.asarray(hretx0, jnp.int32),
            serving=jnp.asarray(a0, jnp.int32),
            ttt=jnp.asarray(ttt0, jnp.int32), t=jnp.int32(0))

    def episode_static(self):
        """Read the per-episode radio inputs (``EpisodeStatic``) off the
        graph: cached SE/CQI/attachment plus the C/P/boresight/fading
        roots.  Pure data -- safe to close over, jit, or vmap against.
        A mesh-built simulator returns its sharded build, whose ``fad``
        is None (no fading)."""
        from repro.mac.engine import EpisodeStatic
        if self.mesh is not None:
            return self._sharded[0]
        return EpisodeStatic(
            se=self.get_spectral_efficiency(), cqi=self.get_CQI(),
            a=self.get_attachment(), C=self.C._data, P=self.P._data,
            bore=self.boresight._data, fad=self.fading._data)

    def episode_fns(self, mobility_step_m=None, per_tti_fading: bool = False,
                    use_harq=None, mesh=None, ue_axis=None,
                    cell_axis=None, radio_mode=None,
                    mobility_move_frac=None, inc_backend=None,
                    telemetry: bool = False, churn=None, relax=None,
                    faults=None):
        """The pure ``(step, rollout)`` episode functions for this
        simulator's topology and MAC parameters (``EpisodeFns``), cached
        per trace-time switch combination.  Both are jit-compiled and
        vmap-compatible: N parallel episodes = ``vmap`` over the state
        (see ``repro.env.CrrmEnv``).  ``mesh`` shard_maps the rollout over
        the UE axis of a device mesh (``ue_axis`` names the mesh axes,
        ``("ue",)`` by default) for >100k-UE episodes; ``cell_axis``
        additionally shards the cell dimension (a UE x cell mesh) so the
        per-cell radio leaves scale past a single device -- see DESIGN.md
        §Radio-fns and §Million-UE-scaling.  A simulator built with
        ``CRRM(params, mesh=...)`` shards over its own mesh and all of its
        axes: ``mesh`` and ``ue_axis`` default to those, and another mesh,
        other axes or a ``cell_axis`` are refused.
        ``radio_mode="incremental"`` recomputes only dirty UE rows of the
        radio chain inside the scan and ``mobility_move_frac`` bounds the
        per-TTI dirtiness (DESIGN.md §Smart-update-in-scan); both default
        to the corresponding ``CRRM_parameters`` fields.  ``inc_backend``
        selects the dirty-row compute path: ``"xla"`` (default),
        ``"pallas"`` (the fused VMEM-resident kernel; raises if the
        configuration cannot be expressed) or ``"auto"``.  ``telemetry``
        adds a per-TTI KPI pytree to both functions' returns
        (DESIGN.md §Observability); ``churn`` a
        ``sim.mobility.ChurnConfig`` enabling the birth-death UE process
        of the digital-twin serving layer (DESIGN.md
        §Digital-twin-serving); ``relax`` a ``sim.radio.RelaxConfig``
        softening the chain's non-differentiable points for
        gradient-based optimization (DESIGN.md §RL-and-differentiability);
        ``faults`` a ``sim.faults.FaultConfig`` in-scan cell fault
        process (DESIGN.md §Fault-injection-and-self-healing; defaults
        to ``params.faults``, ``0`` forces off) -- all off, the exact
        legacy program."""
        from repro.mac import engine as mac_engine
        if isinstance(ue_axis, str):
            ue_axis = (ue_axis,)
        if self.mesh is not None:
            axes = tuple(self.mesh.axis_names)
            mesh = self.mesh if mesh is None else mesh
            if (mesh != self.mesh or ue_axis not in (None, axes)
                    or cell_axis is not None):
                raise ValueError(
                    f"this simulator was built on a mesh with axes {axes} "
                    f"over the UE rows; its episodes run on that mesh and "
                    f"those axes only")
            ue_axis = axes
        elif ue_axis is None:
            ue_axis = ("ue",)
        return mac_engine.episode_fns_for(
            self, mobility_step_m=mobility_step_m,
            per_tti_fading=per_tti_fading, use_harq=use_harq,
            mesh=mesh, ue_axis=ue_axis, cell_axis=cell_axis,
            radio_mode=radio_mode,
            mobility_move_frac=mobility_move_frac,
            inc_backend=inc_backend, telemetry=telemetry,
            churn=churn, relax=relax, faults=faults)

    def sync_episode_state(self, state, positions: bool = False) -> None:
        """Write a final ``EpisodeState`` back into the graph (legacy
        mutate/query convenience -- functional callers thread the state
        instead).  ``positions`` also writes the UE positions root (only
        meaningful after a mobility episode)."""
        if positions:
            self.set_UE_positions(state.U)
        self.buffer.set(state.backlog)
        self._pf_avg = state.pf_avg
        self.sched.cursor = int(state.rr_cursor)
        self._harq_bits, self._harq_retx = state.harq_bits, state.harq_retx
        if self.params.ho_enabled:
            self._ho_serving, self._ho_ttt = state.serving, state.ttt

    def reset_episode_state(self) -> None:
        """Drop persisted episode state (PF EWMA, HARQ, serving cells) so
        the next ``init_episode_state`` re-seeds from the graph."""
        for attr in ("_pf_avg", "_harq_bits", "_harq_retx",
                     "_ho_serving", "_ho_ttt"):
            if hasattr(self, attr):
                delattr(self, attr)

    def run_episode(self, n_tti: int, key=None, mobility_step_m=None,
                    per_tti_fading: bool = False, sync_state: bool = True,
                    use_harq=None, radio_mode=None,
                    mobility_move_frac=None, telemetry: bool = False):
        """Roll ``n_tti`` TTIs as one ``lax.scan`` program.

        Returns (n_tti, n_ues) delivered throughput in bits/s -- or
        ``(tput, telem)`` with ``telemetry=True``, ``telem`` being the
        stacked per-TTI ``repro.obs.Telemetry`` KPI pytree.  A thin
        wrapper over the functional episode API: ``init_episode_state`` ->
        ``episode_fns().rollout`` -> ``sync_episode_state`` (the
        write-back runs unless ``sync_state=False``; new code should use
        the functional API and thread ``EpisodeState`` explicitly).
        ``use_harq`` overrides the ``harq_bler > 0`` auto-switch for the
        stop-and-wait HARQ machine (False selects the legacy Bernoulli
        HARQ-lite).
        """
        from repro.mac import engine as mac_engine
        return mac_engine.run_episode(
            self, n_tti, key=key, mobility_step_m=mobility_step_m,
            per_tti_fading=per_tti_fading, sync_state=sync_state,
            use_harq=use_harq, radio_mode=radio_mode,
            mobility_move_frac=mobility_move_frac, telemetry=telemetry)

    # -------------------------------------------------------------- introspection
    def update_counts(self):
        return self.graph.stats()
