"""Backend-pluggable FL round engines.

One FL round = local training on every sampled client, ``client_compress``
per client, aggregation, server update. ``RoundEngine`` owns the jitted
round function for a (FLConfig, CompressionConfig, loss) triple; the
simulator drives it and keeps the host-side bookkeeping (ledger, sampling,
adaptive tau).

Three backends share every numeric path through ``repro.core``:

``vmap``   — all clients live on one device; the per-client axis is a plain
             vmap. The seed behaviour, still the default.
``shard``  — sampled clients are laid out over a 1-D device mesh (axis
             ``clients``, built by ``launch.mesh.make_client_mesh``); each
             shard vmaps its local clients, the aggregate is a psum over
             the mesh axis, and the per-client upload nnz comes back
             sharded so ``CommLedger`` accounting stays exact.
``async``  — buffered asynchronous aggregation (FedBuff-style): each tick
             dispatches the sampled cohort against the *current* model,
             payloads spend a sampled delay in flight
             (``fl/availability.py``), and the server applies an update as
             soon as ``buffer_size`` payloads are waiting — each weighted
             by the scheme's ``staleness`` stage. The client and server
             halves are the vmap engine's ``_client_update`` /
             ``_server_update`` verbatim, so with zero delays and
             ``buffer_size == cohort`` a tick IS the vmap round, bitwise.

On a single device vmap and shard are bitwise identical (same vmap trace,
psum of one shard is the identity) — asserted by tests/test_engine.py; the
async zero-delay identity is asserted by tests/test_async.py.

Orthogonal to the backend axis, ``FLConfig.topology`` selects the wire
graph (``repro.topo``): ``star`` keeps the engines above untouched, while
``ring`` and ``hierarchical`` route to :class:`TopologyEngine` — one
jitted round function per topology that drives the same ``_client_update``
/ ``_server_update`` numerics through segmented ring passing or two-tier
re-compression. ``ring(k=0)`` and ``hierarchical(groups=1)`` are
bitwise-identical to ``star`` (tests/test_topology.py).

Round function signature (both synchronous backends; the async engine
splits the same computation into a jitted dispatch half and a jitted
buffered-apply half — see ``AsyncBufferedEngine``):

    round_fn(params, cstates, sstate, gbar_prev, client_idx, batches,
             round_idx, lr, tau_now[, rates, wire_levels])
      -> (params, cstates, sstate, bcast, upload_nnz[k], download_nnz,
          union_nnz)

``download_nnz`` is the POST-downlink broadcast nnz (what the ledger
charges K-unicast); ``union_nnz`` is the pre-downlink sparse union, the
mask-overlap signal the adaptive-tau controller consumes — with
``downlink=none`` the two are identical.

The optional trailing ``rates`` ([k] float32 per-client effective rates)
and ``wire_levels`` ([k] int32 wire-dtype levels) exist only under an
adaptive ``rate_control`` stage — the simulator computes them host-side
each round (``repro.core.rate_control``) and the engines thread them into
``client_compress``. The fixed controller never passes them, so the
9-argument call traces the exact legacy jaxpr (bitwise controller-off
path; goldens can never drift because the controller exists). Stochastic
wire codecs (``probquant``) additionally get the sampled ``client_idx``
threaded as ``client_id`` so vmapped clients draw independent PRNG
streams — again a static branch, keyed on ``scheme.wire.stochastic``.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import (
    gather_client_states,
    group_sum,
    interleave_position_stacks,
    resolve,
    resolve_tier,
    scatter_client_states,
    stack_client_states,
)
from repro.obs import trace
from repro.topo import (
    TOPOLOGIES,
    HierarchicalLayout,
    RingLayout,
    TopoRoundInfo,
    inject_incoming,
)
from repro.utils import tree_map, tree_zeros_like

BACKENDS = ("vmap", "shard", "async")


class RoundEngine:
    """Owns the compiled round step for one backend.

    The compression scheme is consumed as a protocol object
    (``repro.core.resolve(comp_cfg)``): the engine never branches on scheme
    names — mask-based presets and the sketch-based FetchSGD preset run
    through the same round function.
    """

    name = "base"

    def __init__(self, fl_cfg, comp_cfg, loss_fn: Callable, sampled_per_round: int):
        self.fl = fl_cfg
        self.comp = comp_cfg
        self.scheme = resolve(comp_cfg)
        self.loss_fn = loss_fn
        self.sampled_per_round = sampled_per_round
        # Static rate-control layout flags (decided at build time, never
        # traced): whether the simulator threads per-client rates, whether
        # per-client wire levels ride along, and whether the wire codec
        # needs client ids for decorrelated PRNG streams.
        self.rate_adaptive = self.scheme.rate_adaptive
        self.use_levels = (
            self.rate_adaptive
            and float(getattr(comp_cfg, "rate_wire_threshold", 0.0)) > 0.0)
        self.thread_client_ids = self.scheme.wire.stochastic
        self.round_fn = jax.jit(self._build())

    # ------------------------------------------------------------------

    def _grads(self, params, batches):
        """Local gradients for a stack of clients (leading axis)."""
        with trace.annotate_scope("round.client_grads"):
            grad_fn = jax.grad(self.loss_fn)
            return jax.vmap(grad_fn, in_axes=(None, 0))(params, batches)

    def _compress_stack(self, states, grads, gbar_prev, round_idx, tau_now,
                        client_ids=None, rates=None, levels=None):
        """``client_compress`` vmapped over a stack of clients.

        The trailing extras (each ``None`` or a [k] array vmapped alongside
        the client axis) are the rate-control inputs; with all three absent
        this is byte-identical to the pre-rate-control trace."""
        with trace.annotate_scope("round.client_compress"):
            compress = self.scheme.client_compress
            tau_kw = {"tau_override": tau_now} if self.fl.adaptive_tau else {}
            extras, names = [], []
            for name, arr in (("client_id", client_ids), ("rate", rates),
                              ("wire_level", levels)):
                if arr is not None:
                    extras.append(arr)
                    names.append(name)
            if not extras:
                return jax.vmap(
                    lambda st, g: compress(st, g, gbar_prev, round_idx, **tau_kw)
                )(states, grads)
            return jax.vmap(
                lambda st, g, *ex: compress(
                    st, g, gbar_prev, round_idx, **tau_kw,
                    **dict(zip(names, ex, strict=True)))
            )(states, grads, *extras)

    def _client_update(self, params, states, batches, gbar_prev, round_idx,
                       tau_now, client_ids=None, rates=None, levels=None):
        """Local gradients + compression for a stack of clients (leading
        axis). Shared verbatim by every backend and topology so their
        numerics can never drift: the shard backend calls this on each
        shard's slice, the topology engine per tier/ring position.

        The ``named_scope``s are trace-time annotations (zero runtime
        cost) that name these sections in XLA profiles, lining up with
        the host-side ``obs.trace`` spans around the dispatch."""
        grads = self._grads(params, batches)
        G, new_states, infos = self._compress_stack(
            states, grads, gbar_prev, round_idx, tau_now,
            client_ids=client_ids, rates=rates, levels=levels)
        return G, new_states, infos

    def _server_update(self, params, sstate, g_sum, lr, num_contributors=None):
        n = float(self.sampled_per_round if num_contributors is None
                  else num_contributors)
        with trace.annotate_scope("round.server_aggregate"):
            bcast, sstate, ainfo = self.scheme.server_aggregate(
                sstate, g_sum, n, lr=lr, params=params
            )
        with trace.annotate_scope("round.apply_update"):
            if self.scheme.owns_lr:
                # e.g. FetchSGD: lr already entered the sketch-space error
                # feedback — the broadcast IS the finished update.
                params = tree_map(lambda w, g: w - g.astype(w.dtype), params, bcast)
            else:
                params = tree_map(lambda w, g: w - lr * g.astype(w.dtype), params, bcast)
        return params, sstate, bcast, ainfo

    def _build(self):
        raise NotImplementedError


class VmapEngine(RoundEngine):
    """Single-device path: one vmap over all sampled clients."""

    name = "vmap"

    def _build(self):
        thread_ids = self.thread_client_ids

        def round_fn(params, cstates, sstate, gbar_prev, client_idx, batches,
                     round_idx, lr, tau_now, rates=None, wire_levels=None):
            sampled = gather_client_states(cstates, client_idx)
            G, new_states, infos = self._client_update(
                params, sampled, batches, gbar_prev, round_idx, tau_now,
                client_ids=client_idx if thread_ids else None,
                rates=rates, levels=wire_levels,
            )
            cstates = scatter_client_states(cstates, client_idx, new_states)
            g_sum = tree_map(lambda x: jnp.sum(x, axis=0), G)
            params, sstate, bcast, ainfo = self._server_update(params, sstate, g_sum, lr)
            return (params, cstates, sstate, bcast, infos.upload_nnz,
                    ainfo.download_nnz, ainfo.union_nnz)

        return round_fn


class ShardMapEngine(RoundEngine):
    """Multi-device path: clients sharded over the ``clients`` mesh axis.

    Gather/scatter of the full per-client state stack and the server step
    stay outside the shard_map (replicated); only the per-client hot loop —
    local grads, compression, partial aggregation — runs per shard.
    """

    name = "shard"

    def __init__(self, fl_cfg, comp_cfg, loss_fn, sampled_per_round, mesh=None):
        if mesh is None:
            from repro.launch.mesh import make_client_mesh

            mesh = make_client_mesh(getattr(fl_cfg, "shards", 0))
        self.mesh = mesh
        (self.num_shards,) = mesh.devices.shape
        if sampled_per_round % self.num_shards != 0:
            raise ValueError(
                f"shard backend needs clients_per_round ({sampled_per_round}) "
                f"divisible by the mesh size ({self.num_shards})"
            )
        super().__init__(fl_cfg, comp_cfg, loss_fn, sampled_per_round)

    def _build(self):
        mesh = self.mesh
        thread_ids = self.thread_client_ids
        adaptive = self.rate_adaptive
        use_levels = self.use_levels

        def shard_body(params, states, batches, gbar_prev, round_idx, tau_now,
                       *extras):
            # Everything here sees only this shard's slice of the client
            # axis; ``extras`` is the statically-shaped tail of per-client
            # rate-control inputs (client ids / rates / levels), each also
            # sharded over the client axis.
            it = iter(extras)
            ids = next(it) if thread_ids else None
            rates = next(it) if adaptive else None
            levels = next(it) if use_levels else None
            G, new_states, infos = self._client_update(
                params, states, batches, gbar_prev, round_idx, tau_now,
                client_ids=ids, rates=rates, levels=levels,
            )
            g_local = tree_map(lambda x: jnp.sum(x, axis=0), G)
            g_sum = jax.lax.psum(g_local, "clients")
            return g_sum, new_states, infos.upload_nnz

        n_extras = int(thread_ids) + int(adaptive) + int(use_levels)
        sharded = jax.shard_map(
            shard_body,
            mesh=mesh,
            in_specs=(P(), P("clients"), P("clients"), P(), P(), P(),
                      *([P("clients")] * n_extras)),
            out_specs=(P(), P("clients"), P("clients")),
            check_vma=False,
        )

        def round_fn(params, cstates, sstate, gbar_prev, client_idx, batches,
                     round_idx, lr, tau_now, rates=None, wire_levels=None):
            sampled = gather_client_states(cstates, client_idx)
            extras = []
            if thread_ids:
                extras.append(client_idx)
            if adaptive:
                extras.append(rates)
            if use_levels:
                extras.append(wire_levels)
            g_sum, new_states, up_nnz = sharded(
                params, sampled, batches, gbar_prev, round_idx, tau_now,
                *extras,
            )
            cstates = scatter_client_states(cstates, client_idx, new_states)
            params, sstate, bcast, ainfo = self._server_update(params, sstate, g_sum, lr)
            return (params, cstates, sstate, bcast, up_nnz,
                    ainfo.download_nnz, ainfo.union_nnz)

        return round_fn


class TopologyEngine(RoundEngine):
    """Non-star wire graphs (``FLConfig.topology``): segmented ring
    passing or two-tier hierarchical aggregation, one jitted round
    function per topology (see ``repro.topo`` for the semantics and the
    star-degeneracy invariants).

    The per-client numerics are the star engines' ``_grads`` /
    ``_compress_stack`` / ``_server_update`` verbatim; this class only
    rewires *who talks to whom*:

    ``ring``          every client computes its gradient, then a static
                      hop loop threads the accumulated payload through
                      each segment (``repro.topo.inject_incoming`` picks
                      the scheme-correct injection seam); segment tails
                      upload, earlier hops are peer traffic. The server
                      broadcast reaches clients every ``sync_every``
                      rounds.
    ``hierarchical``  the leaf tier is the star cohort update unchanged;
                      group sums are re-compressed by the tier scheme
                      (``resolve_tier``) whose per-aggregator ClientState
                      holds the tier's own GMF momentum + EF residual;
                      the cloud divides by the cohort size once.

    ``backend`` selects how the per-client leaf work is laid out:
    ``vmap`` on one device, or ``shard`` over the ``clients`` mesh axis
    (hierarchical shards the whole leaf update; ring shards the gradient
    computation — the hop loop itself crosses segment boundaries, so it
    runs on the replicated stack). The async backend is star-only.
    """

    name = "topo"

    def __init__(self, fl_cfg, comp_cfg, loss_fn, sampled_per_round, mesh=None):
        self.topology = getattr(fl_cfg, "topology", "star")
        if self.topology not in ("ring", "hierarchical"):
            raise ValueError(
                f"TopologyEngine handles ring/hierarchical, got "
                f"{self.topology!r} (star routes to the vmap/shard engines)")
        if resolve(comp_cfg).rate_adaptive:
            raise ValueError(
                "adaptive rate control is star-only: ring hop payloads and "
                "hierarchical tier re-compression have no per-client "
                "server-ingress rate to control; use topology='star' (or "
                "the fixed rate_control stage)")
        self.leaf_backend = getattr(fl_cfg, "backend", "vmap")
        if self.leaf_backend not in ("vmap", "shard"):
            raise ValueError(
                f"topology={self.topology!r} needs backend 'vmap' or "
                f"'shard', got {self.leaf_backend!r}")
        if self.leaf_backend == "shard":
            if mesh is None:
                from repro.launch.mesh import make_client_mesh

                mesh = make_client_mesh(getattr(fl_cfg, "shards", 0))
            self.mesh = mesh
            (self.num_shards,) = mesh.devices.shape
            if sampled_per_round % self.num_shards != 0:
                raise ValueError(
                    f"shard backend needs clients_per_round "
                    f"({sampled_per_round}) divisible by the mesh size "
                    f"({self.num_shards})")
        self.sync_every = int(getattr(fl_cfg, "sync_every", 1))
        if self.topology == "ring":
            self.layout = RingLayout(sampled_per_round,
                                     int(getattr(fl_cfg, "ring_hops", 0)))
        else:
            self.layout = HierarchicalLayout(sampled_per_round,
                                             int(getattr(fl_cfg, "groups", 1)))
            self.tier_scheme = resolve_tier(comp_cfg)
            if self.tier_scheme.is_sketch:
                raise ValueError(
                    "sketch tier schemes are unsupported: the aggregator "
                    "payload must stay model-shaped so the cloud's "
                    "server_aggregate can consume it")
            self.tier_cstates = None  # lazy: needs params shapes
        super().__init__(fl_cfg, comp_cfg, loss_fn, sampled_per_round)

    # ------------------------------------------------------------------

    def _build(self):
        if self.topology == "ring":
            return self._build_ring()
        return self._build_hier()

    def _build_ring(self):
        lay = self.layout
        k1 = lay.hops + 1
        thread_ids = self.thread_client_ids
        pos_idx = [jnp.asarray(lay.position_indices(p)) for p in range(k1)]

        if self.leaf_backend == "shard":
            grads_fn = jax.shard_map(
                lambda params, batches: self._grads(params, batches),
                mesh=self.mesh,
                in_specs=(P(), P("clients")),
                out_specs=P("clients"),
                check_vma=False,
            )
        else:
            grads_fn = self._grads

        def round_fn(params, cstates, sstate, gbar_prev, client_idx, batches,
                     round_idx, lr, tau_now):
            sampled = gather_client_states(cstates, client_idx)
            grads = grads_fn(params, batches)
            incoming = None
            ingress_nnz = None
            state_stacks, peer_nnz = [], []
            for p in range(k1):
                if k1 == 1:
                    st_p, g_p = sampled, grads
                else:
                    take = lambda x, p=p: jnp.take(x, pos_idx[p], axis=0)
                    st_p = tree_map(take, sampled)
                    g_p = tree_map(take, grads)
                st_p, g_p, add_after = inject_incoming(
                    self.scheme, st_p, g_p, incoming)
                ids_p = (jnp.take(client_idx, pos_idx[p]) if thread_ids
                         else None)
                with trace.annotate_scope(f"topo.ring_hop{p}"):
                    G_p, new_st_p, infos_p = self._compress_stack(
                        st_p, g_p, gbar_prev, round_idx, tau_now,
                        client_ids=ids_p)
                if add_after:
                    G_p = tree_map(jnp.add, G_p, incoming)
                incoming = G_p
                state_stacks.append(new_st_p)
                if p < lay.hops:
                    peer_nnz.append(infos_p.upload_nnz)
                else:
                    ingress_nnz = infos_p.upload_nnz
            new_states = interleave_position_stacks(state_stacks)
            cstates = scatter_client_states(cstates, client_idx, new_states)
            g_sum = tree_map(lambda x: jnp.sum(x, axis=0), incoming)
            params, sstate, bcast, ainfo = self._server_update(
                params, sstate, g_sum, lr)
            peer = (jnp.concatenate(peer_nnz) if peer_nnz
                    else jnp.zeros((0,), ingress_nnz.dtype))
            return (params, cstates, sstate, bcast, ingress_nnz, peer,
                    ainfo.download_nnz, ainfo.union_nnz)

        return round_fn

    def _build_hier(self):
        lay = self.layout
        thread_ids = self.thread_client_ids
        tier_ids = self.tier_scheme.wire.stochastic

        if self.leaf_backend == "shard":
            def leaf_body(params, states, batches, gbar_prev, round_idx,
                          tau_now, *extras):
                ids = extras[0] if thread_ids else None
                G, new_states, infos = self._client_update(
                    params, states, batches, gbar_prev, round_idx, tau_now,
                    client_ids=ids)
                return G, new_states, infos.upload_nnz

            leaf_fn = jax.shard_map(
                leaf_body,
                mesh=self.mesh,
                in_specs=(P(), P("clients"), P("clients"), P(), P(), P(),
                          *([P("clients")] * int(thread_ids))),
                out_specs=(P("clients"), P("clients"), P("clients")),
                check_vma=False,
            )
        else:
            def leaf_fn(params, states, batches, gbar_prev, round_idx,
                        tau_now, *extras):
                ids = extras[0] if thread_ids else None
                G, new_states, infos = self._client_update(
                    params, states, batches, gbar_prev, round_idx, tau_now,
                    client_ids=ids)
                return G, new_states, infos.upload_nnz

        def round_fn(params, cstates, tier_cstates, sstate, gbar_prev,
                     client_idx, batches, round_idx, lr, tau_now):
            sampled = gather_client_states(cstates, client_idx)
            leaf_extras = (client_idx,) if thread_ids else ()
            G, new_states, leaf_nnz = leaf_fn(
                params, sampled, batches, gbar_prev, round_idx, tau_now,
                *leaf_extras)
            cstates = scatter_client_states(cstates, client_idx, new_states)
            gsum = group_sum(G, lay.groups)
            with trace.annotate_scope("topo.tier_compress"):
                if tier_ids:
                    # aggregator index doubles as the tier "client" id so
                    # each group's stochastic wire draws its own stream
                    T, tier_cstates, tier_infos = jax.vmap(
                        lambda st, g, gid: self.tier_scheme.client_compress(
                            st, g, gbar_prev, round_idx, client_id=gid)
                    )(tier_cstates, gsum, jnp.arange(lay.groups))
                else:
                    T, tier_cstates, tier_infos = jax.vmap(
                        lambda st, g: self.tier_scheme.client_compress(
                            st, g, gbar_prev, round_idx)
                    )(tier_cstates, gsum)
            g_sum = tree_map(lambda x: jnp.sum(x, axis=0), T)
            params, sstate, bcast, ainfo = self._server_update(
                params, sstate, g_sum, lr)
            return (params, cstates, tier_cstates, sstate, bcast, leaf_nnz,
                    tier_infos.upload_nnz, ainfo.download_nnz,
                    ainfo.union_nnz)

        return round_fn

    # ------------------------------------------------------------------

    def _init_tier_states(self, params):
        tier_client, _ = self.tier_scheme.init_states(params)
        return stack_client_states(tier_client, self.layout.groups)

    def topo_round(self, params, cstates, sstate, gbar_prev, client_idx,
                   batches, round_idx: int, lr, tau_now):
        """One topology round. Returns ``(params, cstates, sstate, bcast,
        info)`` with a :class:`repro.topo.TopoRoundInfo` describing what
        hit which link; the caller gates ``gbar_prev`` and the download
        charges on ``info.synced``."""
        t = int(round_idx)
        synced = ((t + 1) % self.sync_every == 0)
        n = self.sampled_per_round
        if self.topology == "ring":
            (params, cstates, sstate, bcast, ingress, peer, down_nnz,
             union_nnz) = self.round_fn(
                params, cstates, sstate, gbar_prev, jnp.asarray(client_idx),
                batches, jnp.asarray(t), lr, tau_now)
            info = TopoRoundInfo(
                topology="ring",
                ingress_nnz=np.asarray(ingress, np.float64),
                peer_nnz=np.asarray(peer, np.float64),
                down_nnz=float(down_nnz), union_nnz=float(union_nnz),
                synced=synced,
                down_recipients=n if synced else 0,
                relay_recipients=0,
            )
        else:
            if self.tier_cstates is None:
                self.tier_cstates = self._init_tier_states(params)
            (params, cstates, self.tier_cstates, sstate, bcast, leaf_nnz,
             tier_nnz, down_nnz, union_nnz) = self.round_fn(
                params, cstates, self.tier_cstates, sstate, gbar_prev,
                jnp.asarray(client_idx), batches, jnp.asarray(t), lr, tau_now)
            info = TopoRoundInfo(
                topology="hierarchical",
                ingress_nnz=np.asarray(tier_nnz, np.float64),
                peer_nnz=np.asarray(leaf_nnz, np.float64),
                down_nnz=float(down_nnz), union_nnz=float(union_nnz),
                synced=synced,
                down_recipients=self.layout.groups if synced else 0,
                relay_recipients=n if synced else 0,
            )
        return params, cstates, sstate, bcast, info


class AsyncApply(NamedTuple):
    """Host-side record of one buffered server update (one flush)."""

    down_nnz: float      # post-downlink broadcast nnz (ledger download term)
    union_nnz: float     # pre-downlink union (adaptive-tau signal)
    gaps: np.ndarray     # [B] staleness gap per buffered payload
    up_nnz_mean: float   # mean upload nnz of the buffered payloads
    num: int             # buffer size (number of contributors)


class AsyncBufferedEngine(RoundEngine):
    """Asynchronous buffered aggregation (FedBuff semantics, GMF-aware).

    Host-driven round loop: every tick the sampled cohort is *dispatched* —
    local grads + ``client_compress`` against the current params/broadcast
    snapshot (the jitted ``dispatch_fn``, built from the same
    ``_client_update`` the synchronous engines trace) — and each payload is
    assigned a sampled network delay and dropout (``fl/availability.py``).
    Payloads sit in flight until their arrival tick, then queue at the
    server; whenever ``buffer_size`` payloads are waiting the server flushes
    the buffer (the jitted ``apply_fn``): each payload is weighted by the
    scheme's ``staleness`` stage against its gap (apply tick − dispatch
    tick), the weighted stack is summed and handed to ``_server_update``
    verbatim. Several flushes can happen in one tick; none happens while
    the buffer is short.

    For ``gmf_damp`` staleness the engine maintains the *server-held global
    momentum* — a normalized EMA of broadcasts, ``M ← β·M + (1−β)·Ĝ`` with
    the scheme's ``beta``, so M lives on the broadcast's own scale — which
    the stage blends into stale payloads (the paper's fusion direction,
    applied on the server side of the protocol).

    Key invariant (tests/test_async.py): with the ``none`` delay model and
    ``buffer_size == cohort size``, every tick dispatches, buffers and
    flushes the exact synchronous cohort in order, so params, states,
    broadcast and ledger totals are **bitwise identical** to the vmap
    engine — goldens can never drift because the async path exists.

    Memory note: queued payloads are stored host-side, sparse-encoded
    (nonzero values + int32 indices, values held in the scheme's wire
    dtype when that round-trips losslessly) and decoded lazily at flush,
    so queue memory scales with ~cohort·(mean_delay+1)·nnz rather than
    full model copies. Dense payloads (sketches, low compression) fall
    back to a plain host array, so the worst case stays one model copy
    per queued payload. The encoding is exact — flush results are pinned
    bitwise-equal to the dense-queue path (``encode_queue = False``) in
    tests/test_async.py.
    """

    name = "async"

    def __init__(self, fl_cfg, comp_cfg, loss_fn, sampled_per_round):
        from repro.fl import availability as _avail

        self.buffer_size = int(getattr(fl_cfg, "buffer_size", 0) or
                               sampled_per_round)
        if self.buffer_size < 1:
            raise ValueError(
                f"buffer_size must be >= 1, got {self.buffer_size}")
        super().__init__(fl_cfg, comp_cfg, loss_fn, sampled_per_round)
        self.availability = _avail.from_fl_config(fl_cfg)
        self.apply_fn = jax.jit(self._build_apply())
        self._rng = np.random.default_rng(fl_cfg.seed + 2)
        self._inflight: list[dict] = []   # dispatched, not yet arrived
        self._pending: list[dict] = []    # arrived, waiting for a flush
        self._gmom = None                 # server-held global momentum (lazy)
        self._seq = 0                     # dispatch order tiebreaker
        # per-arrival value-byte costs of the last tick (aligned with the
        # arrived_nnz array async_round returns) — the simulator's ledger
        # override under adaptive wire-level control
        self.last_arrived_value_bytes = np.zeros(0, np.float64)
        # Queue payloads sparse/wire-encoded on the host (memory ~ nnz,
        # not params). False keeps the legacy dense device-array queue —
        # the reference the bitwise pin test compares against.
        self.encode_queue = True
        self._store_dtype = self._wire_storage_dtype()

    def _wire_storage_dtype(self):
        """Host dtype queued values are stored in. Safe to narrow only
        when the wire round-trip already quantised the values to that
        dtype (float16/bfloat16 cast wires): the narrowing cast is then
        bitwise-invertible. int8-wire values are *dequantised* floats, so
        they (and the exact float32 wire) stay float32."""
        wire = self.scheme.wire.name
        if wire == "float16":
            return np.dtype(np.float16)
        if wire == "bfloat16":
            try:
                import ml_dtypes

                return np.dtype(ml_dtypes.bfloat16)
            except ImportError:  # pragma: no cover - jax ships ml_dtypes
                return np.dtype(np.float32)
        return np.dtype(np.float32)

    # -- host-side queue codec -----------------------------------------

    def _encode_payload(self, host_stack_leaves, treedef, i):
        """Encode client ``i``'s payload from the host-fetched dispatch
        stack: per leaf, nonzero values + flat indices (or a dense host
        copy when sparse encoding would not pay)."""
        enc = []
        for x in host_stack_leaves:
            arr = np.asarray(x[i])
            flat = arr.reshape(-1)
            idx = np.flatnonzero(flat)
            # sparse = values + indices per entry; dense = one value per
            # entry. Crossover at 50% density, same as the wire cost model.
            if 2 * idx.size >= flat.size:
                enc.append(("dense", arr.astype(self._store_dtype),
                            arr.shape, arr.dtype))
            else:
                idx_dtype = np.int32 if flat.size < 2**31 else np.int64
                enc.append(("sparse", idx.astype(idx_dtype),
                            flat[idx].astype(self._store_dtype),
                            arr.shape, arr.dtype))
        return {"treedef": treedef, "leaves": enc}

    @staticmethod
    def _decode_payload(rec):
        leaves = []
        for e in rec["leaves"]:
            if e[0] == "dense":
                _, vals, shape, dtype = e
                leaves.append(np.asarray(vals, dtype=dtype).reshape(shape))
            else:
                _, idx, vals, shape, dtype = e
                flat = np.zeros(int(np.prod(shape)), dtype=dtype)
                flat[idx] = vals.astype(dtype)
                leaves.append(flat.reshape(shape))
        return jax.tree_util.tree_unflatten(rec["treedef"], leaves)

    # ------------------------------------------------------------------

    def _build(self):
        thread_ids = self.thread_client_ids

        def dispatch_fn(params, cstates, gbar_prev, client_idx, batches,
                        round_idx, tau_now, rates=None, wire_levels=None):
            sampled = gather_client_states(cstates, client_idx)
            G, new_states, infos = self._client_update(
                params, sampled, batches, gbar_prev, round_idx, tau_now,
                client_ids=client_idx if thread_ids else None,
                rates=rates, levels=wire_levels,
            )
            cstates = scatter_client_states(cstates, client_idx, new_states)
            return G, cstates, infos.upload_nnz

        return dispatch_fn

    def _build_apply(self):
        def apply_fn(params, sstate, buf, gaps, gmom, lr):
            buf = self.scheme.apply_staleness(buf, gaps, gmom)
            g_sum = tree_map(lambda x: jnp.sum(x, axis=0), buf)
            params, sstate, bcast, ainfo = self._server_update(
                params, sstate, g_sum, lr, num_contributors=self.buffer_size
            )
            if self.scheme.staleness_momentum:
                # Normalized EMA (β·M + (1−β)·Ĝ), unlike the client-side
                # fusion M: gmf_damp adds M to payloads RAW (no l2
                # normalisation shields it), so it must live on the
                # broadcast's own scale — the unnormalized form is
                # ~1/(1−β) times larger and destabilises stale flushes.
                gmom = tree_map(
                    lambda mm, b: self.comp.beta * mm + (1.0 - self.comp.beta) * b,
                    gmom, bcast)
            return (params, sstate, bcast, gmom, ainfo.download_nnz,
                    ainfo.union_nnz)

        return apply_fn

    # ------------------------------------------------------------------

    def async_round(self, params, cstates, sstate, gbar_prev, client_idx,
                    batches, round_idx: int, lr, tau_now, rates=None,
                    wire_levels=None):
        """One server tick: dispatch the cohort, land arrivals, flush full
        buffers. Returns ``(params, cstates, sstate, gbar_prev,
        arrived_nnz, applies)`` where ``arrived_nnz`` is the np array of
        upload nnz that hit the wire this tick (ledger upload term) and
        ``applies`` is a list of :class:`AsyncApply`, one per flush.

        ``rates``/``wire_levels`` are the adaptive controller's per-client
        outputs for THIS dispatch (None under the fixed controller). A
        payload's wire-level — and hence its per-value byte cost — is fixed
        at dispatch; it rides the in-flight record so the ledger can charge
        the right bytes when the payload actually arrives
        (``last_arrived_value_bytes``, aligned with ``arrived_nnz``)."""
        t = int(round_idx)
        k = len(client_idx)
        if self._gmom is None:
            self._gmom = (tree_zeros_like(params)
                          if self.scheme.staleness_momentum else {})

        # -- dispatch: clients pull the current model, do local work -------
        with trace.span("tick/dispatch"):
            if rates is None and wire_levels is None:
                G, cstates, up_nnz = self.round_fn(
                    params, cstates, gbar_prev, jnp.asarray(client_idx),
                    batches, jnp.asarray(t), tau_now,
                )
            else:
                G, cstates, up_nnz = self.round_fn(
                    params, cstates, gbar_prev, jnp.asarray(client_idx),
                    batches, jnp.asarray(t), tau_now, rates, wire_levels,
                )
        delays = self.availability.sample_delays(self._rng, k)
        drops = self.availability.sample_dropout(self._rng, k)
        up_nnz_host = np.asarray(up_nnz, np.float64)
        base_vb = float(self.scheme.wire.value_bytes)
        if wire_levels is not None:
            vb_host = np.where(np.asarray(wire_levels) > 0, 1.0, base_vb)
        else:
            vb_host = np.full(k, base_vb)
        host_leaves = treedef = None
        if self.encode_queue and not all(drops):
            # one device->host transfer for the whole dispatch stack, then
            # per-payload sparse encoding off the host copy
            host_stack = jax.device_get(G)
            host_leaves, treedef = jax.tree_util.tree_flatten(host_stack)
        for i in range(k):
            if drops[i]:
                continue
            if self.encode_queue:
                payload = self._encode_payload(host_leaves, treedef, i)
            else:
                payload = tree_map(lambda x, i=i: x[i], G)
            self._inflight.append({
                "arrival": t + int(delays[i]),
                "dispatch": t,
                "seq": self._seq,
                "payload": payload,
                "enc": self.encode_queue,
                "nnz": float(up_nnz_host[i]),
                "vb": float(vb_host[i]),
            })
            self._seq += 1

        # -- arrivals: deterministic (arrival tick, dispatch order) --------
        landed = sorted((r for r in self._inflight if r["arrival"] <= t),
                        key=lambda r: (r["arrival"], r["seq"]))
        self._inflight = [r for r in self._inflight if r["arrival"] > t]
        self._pending.extend(landed)
        arrived_nnz = np.asarray([r["nnz"] for r in landed], np.float64)
        self.last_arrived_value_bytes = np.asarray(
            [r.get("vb", base_vb) for r in landed], np.float64)

        # -- flush every full buffer ---------------------------------------
        applies: list[AsyncApply] = []
        while len(self._pending) >= self.buffer_size:
            chunk = self._pending[: self.buffer_size]
            self._pending = self._pending[self.buffer_size:]
            with trace.span("tick/flush"):
                payloads = [
                    self._decode_payload(r["payload"]) if r.get("enc")
                    else r["payload"]
                    for r in chunk
                ]
                buf = tree_map(lambda *xs: jnp.stack(xs), *payloads)
                gaps = np.asarray([t - r["dispatch"] for r in chunk], np.float64)
                params, sstate, bcast, self._gmom, down_nnz, union_nnz = (
                    self.apply_fn(params, sstate, buf,
                                  jnp.asarray(gaps, jnp.float32),
                                  self._gmom, lr))
            gbar_prev = bcast
            applies.append(AsyncApply(
                down_nnz=float(down_nnz), union_nnz=float(union_nnz),
                gaps=gaps,
                up_nnz_mean=float(np.mean([r["nnz"] for r in chunk])),
                num=self.buffer_size,
            ))
        return params, cstates, sstate, gbar_prev, arrived_nnz, applies

    @property
    def pending(self) -> int:
        """Arrived payloads waiting for a flush (diagnostics)."""
        return len(self._pending)

    @property
    def in_flight(self) -> int:
        """Dispatched payloads still in the network (diagnostics)."""
        return len(self._inflight)


def make_engine(fl_cfg, comp_cfg, loss_fn, sampled_per_round, *, mesh=None) -> RoundEngine:
    """Factory keyed on ``fl_cfg.backend`` (default ``vmap``) and
    ``fl_cfg.topology`` (default ``star`` — the untouched star engines)."""
    backend = getattr(fl_cfg, "backend", "vmap")
    topology = getattr(fl_cfg, "topology", "star")
    if topology not in TOPOLOGIES:
        raise ValueError(
            f"unknown topology {topology!r}; choose from {TOPOLOGIES}")
    if topology != "star":
        if backend == "async":
            raise ValueError(
                "the async buffered engine is star-only; use backend='vmap' "
                "or 'shard' with non-star topologies")
        return TopologyEngine(fl_cfg, comp_cfg, loss_fn, sampled_per_round,
                              mesh=mesh)
    if backend == "vmap":
        return VmapEngine(fl_cfg, comp_cfg, loss_fn, sampled_per_round)
    if backend == "shard":
        return ShardMapEngine(fl_cfg, comp_cfg, loss_fn, sampled_per_round, mesh=mesh)
    if backend == "async":
        return AsyncBufferedEngine(fl_cfg, comp_cfg, loss_fn, sampled_per_round)
    raise ValueError(f"unknown FL backend {backend!r}; choose from {BACKENDS}")
