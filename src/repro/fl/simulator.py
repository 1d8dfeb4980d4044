"""Hub-and-spoke federated-learning simulator (paper §4 experiments).

One process simulates K clients + server. The per-round compute (client
local training, the compression scheme, aggregation, model update) lives in
a pluggable ``RoundEngine`` (fl/engine.py): the ``vmap`` backend runs all
clients on one device, the ``shard`` backend lays the sampled clients out
over a device mesh with ``shard_map`` + psum aggregation, and the ``async``
backend runs buffered asynchronous aggregation — sampled network delays
and dropouts per payload (fl/availability.py), a server flush whenever
``buffer_size`` payloads are waiting, staleness-weighted by the scheme's
``staleness`` stage. Communication is accounted *exactly* via the nnz
counts the schemes emit (upload per client, union/download at the server)
— identically on all backends; async runs additionally emit a per-update
staleness histogram into the ledger.

Supports partial participation (Shakespeare: sample 10 of 100 per round):
sampled clients' states are gathered, compressed, and scattered back —
non-participants keep V/U/M untouched, exactly like real FL.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import CommLedger, CompressionConfig, init_states
from repro.core import adaptive, sparsify, stack_client_states
from repro.fl import availability as _availability
from repro.fl.engine import BACKENDS, make_engine
from repro.obs import health as obs_health
from repro.obs import metrics as obs_metrics
from repro.obs import trace
from repro.topo import validate_fl_topology
from repro.utils import tree_size, tree_zeros_like


@dataclasses.dataclass
class FLConfig:
    num_clients: int
    rounds: int
    clients_per_round: int = 0  # 0 → all
    batch_size: int = 64
    learning_rate: float = 0.1
    lr_decay_rounds: int = 0    # halve lr every N rounds (0 = constant)
    seed: int = 0
    eval_every: int = 10
    # Round-engine backend: "vmap" (single device) | "shard" (device mesh)
    # | "async" (buffered asynchronous aggregation, fl/engine.py).
    backend: str = "vmap"
    shards: int = 0             # shard backend: mesh size (0 → all devices)
    # Async backend: the server flushes a buffer as soon as this many
    # payloads are waiting (0 → cohort size, the synchronous limit) ...
    buffer_size: int = 0
    # ... and each dispatched payload draws a delay/dropout from the
    # availability model (fl/availability.py; means in server ticks).
    delay_model: str = "none"   # none | uniform | geometric | lognormal
    delay_mean: float = 0.0
    delay_max: int = 0          # clip every delay draw (0 = uncapped)
    dropout_rate: float = 0.0   # per-payload P(never arrives)
    # ✦ beyond-paper: closed-loop fusion-ratio control (core/adaptive.py)
    adaptive_tau: bool = False
    tau_target_overlap: float = 0.8
    tau_eta: float = 0.15
    tau_max: float = 0.9
    # Wire-graph topology (repro.topo): "star" (hub-and-spoke, the
    # untouched engines) | "ring" (segmented client→client passing,
    # RingFed-style) | "hierarchical" (two-tier edge aggregation with a
    # tier re-compression scheme, CompressionConfig.tier_scheme).
    topology: str = "star"
    ring_hops: int = 0          # ring: payload handoffs per segment
    sync_every: int = 1         # ring/hier: broadcast reaches clients every N rounds
    groups: int = 1             # hierarchical: number of edge aggregators

    def __post_init__(self):
        if self.backend not in BACKENDS:
            raise ValueError(
                f"unknown backend {self.backend!r}; choose from {BACKENDS}"
            )
        if self.buffer_size < 0:
            raise ValueError(f"buffer_size must be >= 0, got {self.buffer_size}")
        validate_fl_topology(self)
        # Validate the availability fields eagerly (same checks the engine
        # would hit at construction, but with the config's field names).
        from repro.fl import availability as _avail

        _avail.from_fl_config(self)


class FLSimulator:
    """Generic over (model params, loss_fn(params, batch) -> scalar)."""

    def __init__(
        self,
        fl_cfg: FLConfig,
        comp_cfg: CompressionConfig,
        init_fn: Callable[[jax.Array], dict],
        loss_fn: Callable[[dict, tuple], jax.Array],
        eval_fn: Callable[[dict], float] | None = None,
        *,
        mesh=None,
    ):
        self.fl = fl_cfg
        self.comp = comp_cfg
        self.loss_fn = loss_fn
        self.eval_fn = eval_fn
        key = jax.random.PRNGKey(fl_cfg.seed)
        self.params = init_fn(key)
        self.total_params = tree_size(self.params)
        k = fl_cfg.clients_per_round or fl_cfg.num_clients
        self.sampled_per_round = k
        # Per-client compression state, stacked over ALL clients.
        cstate1, self.sstate = init_states(comp_cfg, self.params)
        self.cstates = stack_client_states(cstate1, fl_cfg.num_clients)
        self.gbar_prev = tree_zeros_like(self.params)
        self.history: list[dict] = []
        self.tau_ctl = adaptive.init(comp_cfg.tau if not fl_cfg.adaptive_tau else 0.0)
        self.engine = make_engine(fl_cfg, comp_cfg, loss_fn, k, mesh=mesh)
        # Ledger cost model comes from the scheme's wire stage (16-bit wire
        # payloads are charged 2 bytes/value; sketch uploads are value-only).
        self.ledger = CommLedger(self.engine.scheme.cost_model())
        self._round_fn = self.engine.round_fn
        self._rng = np.random.default_rng(fl_cfg.seed + 1)
        # ✦ beyond-paper: adaptive per-client rate control (the scheme's
        # ``rate_control`` stage, repro.core.rate_control). Everything here
        # is gated on the engine's static flag so the fixed-controller path
        # allocates nothing and draws nothing — cohort sampling and batch
        # RNG streams stay identical between fixed and adaptive runs.
        self.rate_adaptive = self.engine.rate_adaptive
        if self.rate_adaptive:
            self.rate_state = self.engine.scheme.rate_control.init(
                comp_cfg, fl_cfg.num_clients)
            self._bw_rng = np.random.default_rng(fl_cfg.seed + 3)
            self._avail = _availability.from_fl_config(fl_cfg)
            self._last_gap = 0.0  # async: previous tick's mean applied gap
            self._signal_fn = jax.jit(self._build_signal_fn())
            self._rate_update = jax.jit(self._build_rate_update())

    # -- adaptive rate control -----------------------------------------

    def _build_signal_fn(self):
        """Jitted per-round controller signal: each sampled client's
        EF-residual mass over the global delta norm,
        ``‖V_k‖ / (‖Ĝ_prev‖ + eps)`` (float32; exact zeros for schemes
        without an EF state — the controller then sees a flat signal and
        stays at the fixed point)."""
        eps = float(self.comp.eps)

        def signal(cstates, gbar_prev, ids):
            vleaves = jax.tree_util.tree_leaves(cstates.v)
            if not vleaves:
                return jnp.zeros(ids.shape, jnp.float32)
            gsq = sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                      for x in jax.tree_util.tree_leaves(gbar_prev))
            vsq = sum(
                jnp.sum(
                    jnp.square(jnp.take(x, ids, axis=0).astype(jnp.float32)),
                    axis=tuple(range(1, x.ndim)))
                for x in vleaves)
            return jnp.sqrt(vsq) / (jnp.sqrt(gsq) + eps)

        return signal

    def _build_rate_update(self):
        ctrl = self.engine.scheme.rate_control
        comp = self.comp

        def update(state, ids, sig, bandwidth, gap):
            return ctrl.update(comp, state, ids, sig, bandwidth, gap)

        return update

    def _rate_inputs(self, ids, gap: float):
        """One controller step (host-driven, jitted maths): observe the
        signal, draw the bandwidth budget, update the controller state and
        return the round_fn extras ``(rates, levels-or-None)``."""
        ids_j = jnp.asarray(ids)
        sig = self._signal_fn(self.cstates, self.gbar_prev, ids_j)
        bw = self._avail.sample_bandwidth(self._bw_rng, len(ids))
        self.rate_state, rates, levels = self._rate_update(
            self.rate_state, ids_j, sig,
            jnp.asarray(bw, jnp.float32), jnp.asarray(gap, jnp.float32))
        return rates, (levels if self.engine.use_levels else None)

    def _rate_value_bytes(self, levels):
        """Per-client ledger value-byte override for this round's payloads
        (1 byte/value for clients dropped to the int8 wire), or None when
        wire-level control is off."""
        if levels is None:
            return None
        base = float(self.engine.scheme.wire.value_bytes)
        return np.where(np.asarray(levels) > 0, 1.0, base)

    def _rate_obs(self, obs, rates, levels):
        """Publish the controller's decisions: the ``rate.effective``
        series (one observation per sampled client) plus round-event
        extras."""
        r = np.asarray(rates, np.float64)
        for x in r:
            obs.observe("rate.effective", float(x))
        obs.gauge_set("fl.rate_mean", float(r.mean()))
        extra = {"rate_mean": float(r.mean()), "rate_min": float(r.min()),
                 "rate_max": float(r.max())}
        if levels is not None:
            extra["int8_drops"] = int(np.asarray(levels).sum())
        return extra

    # ------------------------------------------------------------------

    def _sample_ids(self, t: int) -> np.ndarray:
        """Cohort sampling, shared verbatim by the sync and async loops so
        the zero-delay async run sees the exact synchronous cohorts."""
        fl = self.fl
        if self.sampled_per_round < fl.num_clients:
            ids = self._rng.choice(fl.num_clients, self.sampled_per_round,
                                   replace=False)
        else:
            ids = np.arange(fl.num_clients)
        return np.sort(ids)

    def _lr_at(self, t: int) -> float:
        fl = self.fl
        lr = fl.learning_rate
        if fl.lr_decay_rounds:
            lr = lr * (0.5 ** (t // fl.lr_decay_rounds))
        return lr

    def run(self, batch_provider, *, log_every: int = 0, on_round=None):
        """batch_provider(round, client_ids, rng) -> stacked batch pytree with
        leading axis len(client_ids)."""
        obs = obs_metrics.get()
        if obs.enabled:
            self._select_gauges(obs)
        if self.engine.name == "async":
            return self._run_async(batch_provider, log_every=log_every,
                                   on_round=on_round)
        if self.engine.name == "topo":
            return self._run_topo(batch_provider, log_every=log_every,
                                  on_round=on_round)
        fl = self.fl
        # One sibling span per host phase of a round, each with round=t.
        # The counts' readback sits between fl.wait and fl.account, inside
        # no span: the wait has already synced on them.
        for t in range(fl.rounds):
            t0 = time.perf_counter()
            up_before = self.ledger.upload_bytes
            down_before = self.ledger.download_bytes
            with trace.span("fl.inputs", round=t):
                ids = self._sample_ids(t)
                ids_d = jnp.asarray(ids)
                t_d = jnp.asarray(t)
                lr_d = jnp.asarray(self._lr_at(t), jnp.float32)
                rates = levels = None
                rate_args = ()
                if self.rate_adaptive:
                    # Synchronous rounds have no staleness: gap = 0.0, which
                    # is also what makes zero-delay async ticks
                    # bitwise-identical. (The controller draws from its own
                    # generator, so it may run before the batches.)
                    rates, levels = self._rate_inputs(ids, 0.0)
                    rate_args = (rates, levels)
            with trace.span("fl.batches", round=t):
                batches = batch_provider(t, ids, self._rng)
            with trace.span("fl.dispatch", round=t):
                (
                    self.params,
                    self.cstates,
                    self.sstate,
                    self.gbar_prev,
                    up_nnz,
                    down_nnz,
                    union_nnz,
                ) = self._round_fn(
                    self.params,
                    self.cstates,
                    self.sstate,
                    self.gbar_prev,
                    ids_d,
                    batches,
                    t_d,
                    lr_d,
                    self.tau_ctl.tau,
                    *rate_args,
                )
            with trace.span("fl.wait", round=t):
                up_nnz, down_nnz, union_nnz = jax.block_until_ready(
                    (up_nnz, down_nnz, union_nnz))
            wall_ms = (time.perf_counter() - t0) * 1e3
            up_host = np.asarray(up_nnz)
            down_host = float(down_nnz)
            # each read is a device-to-host copy: the union only where used
            union_host = (float(union_nnz) if fl.adaptive_tau or obs.enabled
                          else None)
            with trace.span("fl.account", round=t):
                self._account_round(
                    obs, t, len(ids), up_host, down_host, union_host, rates,
                    levels, wall_ms, up_before, down_before, log_every)
            if on_round:
                with trace.span("fl.on_round", round=t):
                    on_round(t, self)
        return self.history

    def _select_gauges(self, obs):
        """How far the per-tensor top-k's grouping engages: one threshold
        search per leaf size (``fl.select_groups``) over ``fl.select_leaves``
        leaves. Set only where the scheme selects that way."""
        if self.engine.scheme.selector.name != "topk" or not self.comp.per_tensor:
            return
        plan = sparsify.select_groups(
            [x.shape for x in jax.tree_util.tree_leaves(self.params)])
        obs.gauge_set("fl.select_groups", len(plan))
        obs.gauge_set("fl.select_leaves", sum(map(len, plan)))

    def _account_round(self, obs, t, cohort, up_host, down_host, union_host,
                       rates, levels, wall_ms, up_before, down_before, log_every):
        """A synchronous round's host bookkeeping on its read-back counts:
        the ledger, adaptive tau, history, evaluation, telemetry, log."""
        fl = self.fl
        # Ledger charges the POST-downlink broadcast (what hits the wire);
        # the adaptive-tau overlap stays defined on the PRE-downlink union
        # so downlink compression cannot alias the mask-alignment signal
        # the controller integrates.
        self.ledger.record_round(
            up_host, down_host, self.total_params, cohort,
            value_bytes=self._rate_value_bytes(levels))
        if fl.adaptive_tau:
            self.tau_ctl = adaptive.update(
                self.tau_ctl,
                float(np.mean(up_host)),
                union_host,
                target_overlap=fl.tau_target_overlap,
                eta=fl.tau_eta,
                tau_max=fl.tau_max,
            )
        rec = {"round": t, "comm_gb": self.ledger.total_gb,
               "tau": float(self.tau_ctl.tau)}
        if self.rate_adaptive:
            rec["rate_mean"] = float(np.asarray(rates).mean())
        if self.eval_fn and (t % fl.eval_every == 0 or t == fl.rounds - 1):
            rec["accuracy"] = float(self.eval_fn(self.params))
        self.history.append(rec)
        if obs.enabled:
            extra = (self._rate_obs(obs, rates, levels)
                     if self.rate_adaptive else None)
            self._record_round_obs(obs, t, rec, wall_ms, up_before, down_before,
                                   float(np.mean(up_host)), down_host,
                                   union_host, extra=extra)
        if log_every and t % log_every == 0:
            acc = rec.get("accuracy")
            acc_s = f" acc={acc:.4f}" if acc is not None else ""
            print(f"[round {t:4d}] comm={self.ledger.total_gb:.4f} GB{acc_s}", flush=True)

    def _record_round_obs(self, obs, t, rec, wall_ms, up_before, down_before,
                          up_nnz_mean, down_nnz, union_nnz, extra=None):
        """Telemetry for one completed round/tick: the ``round`` event
        (wall-clock + this round's wire bytes), the ``fl.tau`` gauge,
        and the compensation-state health block (EF residual
        mass, momentum norms, achieved-vs-target compression, NaN/Inf
        anomaly check on the broadcast). Called only when telemetry is
        enabled — everything here reads already-materialised host values
        except the health norms, which are one jitted bundle."""
        obs.gauge_set("fl.tau", rec["tau"])
        ev = {"round": t, "wall_ms": wall_ms,
              "upload_bytes": self.ledger.upload_bytes - up_before,
              "download_bytes": self.ledger.download_bytes - down_before,
              "upload_nnz_mean": up_nnz_mean, "download_nnz": down_nnz,
              "union_nnz": union_nnz, "tau": rec["tau"]}
        if "accuracy" in rec:
            ev["accuracy"] = rec["accuracy"]
        if extra:
            ev.update(extra)
        obs.event("round", **ev)
        obs_health.record_round_health(
            obs, round_idx=t, cstates=self.cstates, sstate=self.sstate,
            bcast=self.gbar_prev,
            gmom=getattr(self.engine, "_gmom", None),
            upload_nnz_mean=up_nnz_mean, total_params=self.total_params,
            target_rate=self.comp.rate)

    def _run_async(self, batch_provider, *, log_every: int = 0, on_round=None):
        """Asynchronous buffered loop (``backend="async"``).

        One iteration = one server *tick*: the sampled cohort is dispatched
        against the current model, in-flight payloads land, and the engine
        flushes zero or more ``buffer_size`` buffers (fl/engine.py). The
        ledger charges uploads at arrival (what actually hit the wire, so
        dropped payloads are never billed) and downloads per flush (the
        server unicasts the fresh broadcast to that flush's contributors);
        each flush's per-payload staleness gaps land in the ledger's
        histogram. With zero delays and a cohort-sized buffer every tick
        charges exactly what the synchronous ``record_round`` would.
        """
        fl = self.fl
        obs = obs_metrics.get()
        for t in range(fl.rounds):
            t0 = time.perf_counter()
            up_before = self.ledger.upload_bytes
            down_before = self.ledger.download_bytes
            ids = self._sample_ids(t)
            batches = batch_provider(t, ids, self._rng)
            lr = self._lr_at(t)
            rate_args = ()
            if self.rate_adaptive:
                # Staleness signal = the previous tick's mean applied gap
                # (0.0 on the first tick and throughout any zero-delay run,
                # which keeps zero-delay async == sync bitwise).
                rates, levels = self._rate_inputs(ids, self._last_gap)
                rate_args = (rates, levels)
            with trace.span("tick"):
                (
                    self.params,
                    self.cstates,
                    self.sstate,
                    self.gbar_prev,
                    arrived_nnz,
                    applies,
                ) = self.engine.async_round(
                    self.params,
                    self.cstates,
                    self.sstate,
                    self.gbar_prev,
                    ids,
                    batches,
                    t,
                    jnp.asarray(lr, jnp.float32),
                    self.tau_ctl.tau,
                    *rate_args,
                )
                if arrived_nnz.size:
                    # Adaptive runs charge each arrived payload at the wire
                    # level it was dispatched with (the engine tracks
                    # per-record value bytes through the delay queue).
                    vb = (self.engine.last_arrived_value_bytes
                          if self.rate_adaptive else None)
                    self.ledger.record_upload(arrived_nnz, self.total_params,
                                              vb)
                for ap in applies:
                    self.ledger.record_download(ap.down_nnz, self.total_params,
                                                ap.num)
                    self.ledger.record_staleness(ap.gaps)
                    obs.event("flush", round=t,
                              staleness_gaps=[int(g) for g in ap.gaps],
                              down_nnz=ap.down_nnz, union_nnz=ap.union_nnz,
                              up_nnz_mean=ap.up_nnz_mean, num=ap.num)
                    if fl.adaptive_tau:
                        # overlap signal per flush: the buffer's mean upload
                        # nnz against its pre-downlink union, same as one
                        # sync round
                        self.tau_ctl = adaptive.update(
                            self.tau_ctl,
                            ap.up_nnz_mean,
                            ap.union_nnz,
                            target_overlap=fl.tau_target_overlap,
                            eta=fl.tau_eta,
                            tau_max=fl.tau_max,
                        )
                self.ledger.tick()
            wall_ms = (time.perf_counter() - t0) * 1e3
            rec = {"round": t, "comm_gb": self.ledger.total_gb,
                   "tau": float(self.tau_ctl.tau),
                   "applies": len(applies),
                   "pending": self.engine.pending,
                   "in_flight": self.engine.in_flight}
            if self.rate_adaptive:
                rec["rate_mean"] = float(np.asarray(rates).mean())
            if applies:
                gaps = np.concatenate([np.asarray(ap.gaps) for ap in applies])
                rec["staleness_mean"] = float(gaps.mean())
                if self.rate_adaptive:
                    self._last_gap = float(gaps.mean())
            if self.eval_fn and (t % fl.eval_every == 0 or t == fl.rounds - 1):
                rec["accuracy"] = float(self.eval_fn(self.params))
            self.history.append(rec)
            if obs.enabled:
                up_mean = (float(np.mean([ap.up_nnz_mean for ap in applies]))
                           if applies else 0.0)
                down_last = float(applies[-1].down_nnz) if applies else 0.0
                union_last = float(applies[-1].union_nnz) if applies else 0.0
                obs.gauge_set("fl.pending", self.engine.pending)
                obs.gauge_set("fl.in_flight", self.engine.in_flight)
                extra = {"applies": len(applies),
                         "pending": self.engine.pending,
                         "in_flight": self.engine.in_flight}
                if self.rate_adaptive:
                    extra.update(self._rate_obs(obs, rates, levels))
                self._record_round_obs(
                    obs, t, rec, wall_ms, up_before, down_before,
                    up_mean, down_last, union_last, extra=extra)
            if log_every and t % log_every == 0:
                acc = rec.get("accuracy")
                acc_s = f" acc={acc:.4f}" if acc is not None else ""
                print(f"[tick {t:4d}] comm={self.ledger.total_gb:.4f} GB "
                      f"applies={len(applies)} pending={self.engine.pending}"
                      f"{acc_s}", flush=True)
            if on_round:
                on_round(t, self)
        return self.history

    def _run_topo(self, batch_provider, *, log_every: int = 0, on_round=None):
        """Non-star topology loop (``topology="ring" | "hierarchical"``).

        One iteration = one topology round (fl/engine.py TopologyEngine).
        The ledger splits the wire movement per link direction: ring hop
        handoffs and hierarchical leaf→aggregator uploads are *peer*
        bytes, only what reaches the server is *upload* (= server
        ingress) bytes, and the broadcast is charged — server→clients
        for ring, server→aggregators plus the aggregator→leaf peer relay
        for hierarchical — only on sync rounds (``sync_every``), which
        is also when clients actually see the fresh broadcast
        (``gbar_prev`` stays stale in between, RingFed's periodic sync).
        """
        fl = self.fl
        eng = self.engine
        obs = obs_metrics.get()
        for t in range(fl.rounds):
            t0 = time.perf_counter()
            up_before = self.ledger.upload_bytes
            down_before = self.ledger.download_bytes
            peer_before = self.ledger.peer_bytes
            ids = self._sample_ids(t)
            batches = batch_provider(t, ids, self._rng)
            lr = self._lr_at(t)
            with trace.span("round"):
                (self.params, self.cstates, self.sstate, bcast, info) = (
                    eng.topo_round(
                        self.params, self.cstates, self.sstate,
                        self.gbar_prev, ids, batches, t,
                        jnp.asarray(lr, jnp.float32), self.tau_ctl.tau))
                if info.synced:
                    self.gbar_prev = bcast
                if info.peer_nnz.size:
                    self.ledger.record_peer(info.peer_nnz, self.total_params)
                self.ledger.record_upload(info.ingress_nnz, self.total_params)
                if info.synced:
                    self.ledger.record_download(
                        info.down_nnz, self.total_params,
                        info.down_recipients)
                    if info.relay_recipients:
                        self.ledger.record_peer_download(
                            info.down_nnz, self.total_params,
                            info.relay_recipients)
                self.ledger.tick()
            wall_ms = (time.perf_counter() - t0) * 1e3
            ingress_mean = float(np.mean(info.ingress_nnz))
            if fl.adaptive_tau:
                self.tau_ctl = adaptive.update(
                    self.tau_ctl,
                    ingress_mean,
                    float(info.union_nnz),
                    target_overlap=fl.tau_target_overlap,
                    eta=fl.tau_eta,
                    tau_max=fl.tau_max,
                )
            rec = {"round": t, "comm_gb": self.ledger.total_gb,
                   "tau": float(self.tau_ctl.tau),
                   "topology": info.topology, "synced": info.synced,
                   "server_ingress_gb": self.ledger.upload_bytes / 1e9,
                   "peer_gb": self.ledger.peer_bytes / 1e9}
            if self.eval_fn and (t % fl.eval_every == 0 or t == fl.rounds - 1):
                rec["accuracy"] = float(self.eval_fn(self.params))
            self.history.append(rec)
            if obs.enabled:
                obs.event("topo_round", round=t, topology=info.topology,
                          server_ingress_bytes=(
                              self.ledger.upload_bytes - up_before),
                          peer_bytes=self.ledger.peer_bytes - peer_before,
                          synced=info.synced, down_nnz=info.down_nnz)
                self._record_round_obs(
                    obs, t, rec, wall_ms, up_before, down_before,
                    ingress_mean, float(info.down_nnz),
                    float(info.union_nnz),
                    extra={"topology": info.topology, "synced": info.synced,
                           "peer_bytes": (
                               self.ledger.peer_bytes - peer_before)})
                if info.topology == "hierarchical":
                    # aggregator-tier health rides along under its own
                    # gauge prefix: the tier scheme's EF/momentum norms
                    # are where hierarchical compression error lives
                    obs_health.record_round_health(
                        obs, round_idx=t, cstates=eng.tier_cstates,
                        sstate=self.sstate, bcast=bcast,
                        upload_nnz_mean=ingress_mean,
                        total_params=self.total_params,
                        target_rate=self.comp.tier_rate,
                        tier="aggregator")
            if log_every and t % log_every == 0:
                acc = rec.get("accuracy")
                acc_s = f" acc={acc:.4f}" if acc is not None else ""
                print(f"[round {t:4d}] {info.topology} "
                      f"ingress={self.ledger.upload_bytes / 1e9:.4f} GB "
                      f"total={self.ledger.total_gb:.4f} GB"
                      f"{' sync' if info.synced else ''}{acc_s}", flush=True)
            if on_round:
                on_round(t, self)
        return self.history

    def final_accuracy(self) -> float | None:
        for rec in reversed(self.history):
            if "accuracy" in rec:
                return rec["accuracy"]
        return None
