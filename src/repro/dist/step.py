"""Sharded production train/prefill/serve steps with compressed grad-sync.

The distributed runtime maps the paper's FL round onto an SPMD mesh: every
slice of the data-parallel axis acts as one GMF "client". Per-step:

  1. the global batch is viewed as a ``(num_shards, local_batch, ...)``
     stack laid over the sync axis;
  2. each shard computes its local gradient (a vmap row — XLA places it on
     the shard's devices) and runs ``repro.core.client_compress`` on it
     with its own error-feedback state (U, V, M — also laid over the sync
     axis), exactly the code path the FL simulator vmaps over clients;
  3. the masked (and optionally ``wire_dtype``-quantised) gradients ride
     the inter-shard all-reduce — the mean over the stacked axis is the
     only cross-shard collective, and its payload is the sparse union;
  4. ``server_aggregate`` + SGD apply the broadcast update; the broadcast
     is stored as ``gbar`` so every shard's global momentum M stays in
     lock-step (it is built from broadcasts only, as in the paper).

Grad-sync modes (``TrainConfig.grad_sync``):

  dense     — plain data parallelism; no compression state.
  gmf_data  — one GMF client per ``data``-axis slice (single-pod).
  gmf_pod   — one GMF client per ``pod``; dense all-reduce over ``data``
              *inside* each pod, compressed exchange across pods (the
              CFedAvg-style deployment for multi-pod meshes).

Because steps 2–4 reuse ``repro.core.schemes`` verbatim, the distributed
``gmf_data`` step is numerically the explicit-K-clients reference
(tests/dist_check.py asserts it on 8 faked devices).
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import init_states, resolve
from repro.core.state import ClientState, ServerState
from repro.dist import sharding as shr
from repro.optim import sgd
from repro.utils import tree_map, tree_size_scalar, tree_zeros_like

GRAD_SYNC_MODES = ("dense", "gmf_data", "gmf_pod")

# Params sharded over data AND model (FSDP). Threshold picks exactly the
# >40 B archs (qwen2-vl-72b, command-r-plus-104b, kimi-k2-1t); everything
# ≤34 B is TP-only so the per-shard compression state fits next to it.
_FSDP_PARAM_THRESHOLD = 40e9


def needs_fsdp(cfg) -> bool:
    return cfg.param_count() > _FSDP_PARAM_THRESHOLD


class TrainState(NamedTuple):
    params: Any
    opt: Any          # optimiser slots (SGDState)
    cstate: Any       # per-shard compression state, leading sync-axis dim
    sstate: Any       # server-side state (momentum for dgcwgm)
    gbar: Any         # last broadcast Ĝ (feeds the global momentum M)
    step: Any         # scalar int32


def _sync_axis(grad_sync: str) -> str | None:
    if grad_sync == "gmf_data":
        return "data"
    if grad_sync == "gmf_pod":
        return "pod"
    if grad_sync == "dense":
        return None
    raise ValueError(
        f"unknown grad_sync {grad_sync!r}; choose from {GRAD_SYNC_MODES}")


def _num_shards(grad_sync: str, mesh) -> int:
    axis = _sync_axis(grad_sync)
    if axis is None:
        return 1
    if mesh is None:
        return 1  # single-device smoke path: one "client"
    if axis not in mesh.axis_names:
        raise ValueError(f"grad_sync={grad_sync!r} needs a {axis!r} mesh axis "
                         f"(got axes {mesh.axis_names})")
    return mesh.shape[axis]


def _total_params(params):
    # int32 (exact) when it fits, f32 approximation beyond 2^31 elements
    return tree_size_scalar(params)


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------


def make_loss_fn(cfg, mesh=None):
    """Masked-NLL LM loss, ``loss_fn(params, batch) -> (loss, aux)``.

    Positions with label < 0 (VLM patch slots) are excluded from the mean.
    ``aux`` is the router load-balance loss (0 outside MoE), already folded
    into ``loss`` with ``cfg.router_aux_coef``.
    """
    from repro.models import transformer

    ctx = _model_ctx(cfg, mesh)

    def loss_fn(params, batch):
        logits, aux, _ = transformer.forward(cfg, params, batch, ctx=ctx)
        labels = batch["labels"]
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        safe = jnp.maximum(labels, 0)
        nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
        valid = (labels >= 0).astype(jnp.float32)
        loss = jnp.sum(nll * valid) / jnp.maximum(jnp.sum(valid), 1.0)
        return loss + cfg.router_aux_coef * aux, aux

    return loss_fn


def _model_ctx(cfg, mesh, **extra) -> dict:
    """Forward-pass ctx: mesh plumbing for EP MoE (mesh-aware paths only)."""
    ctx: dict = dict(extra)
    if cfg.family == "hybrid":
        # ring caches + masks sized to the local-attention window, matching
        # transformer.init_block_cache
        ctx["window"] = cfg.local_attn_window
    if mesh is not None and cfg.num_experts > 0 and cfg.moe_impl == "ep":
        ctx.update(mesh=mesh, data_axes=shr.dp_axes(mesh),
                   model_axis=shr.MODEL_AXIS, moe_impl="ep",
                   fsdp_moe=needs_fsdp(cfg))
    return ctx


# ---------------------------------------------------------------------------
# Train state
# ---------------------------------------------------------------------------


def init_train_state(cfg, tcfg, ccfg, params, mesh=None) -> TrainState:
    n = _num_shards(tcfg.grad_sync, mesh)
    opt = sgd.init(params, momentum=tcfg.momentum)
    if tcfg.grad_sync == "dense":
        cstate: Any = ClientState(u={}, v={}, m={})
        sstate: Any = ServerState(momentum={}, residual={})
        gbar: Any = {}
    else:
        client, sstate = init_states(ccfg, params)
        cstate = tree_map(
            lambda x: jnp.zeros((n,) + x.shape, x.dtype), client)
        gbar = tree_zeros_like(params) if ccfg.uses_m else {}
    return TrainState(params=params, opt=opt, cstate=cstate, sstate=sstate,
                      gbar=gbar, step=jnp.zeros((), jnp.int32))


def train_state_specs(cfg, tcfg, ccfg, params, mesh) -> TrainState:
    """PartitionSpec tree mirroring ``init_train_state``'s output."""
    pspec = shr.param_specs(params, fsdp=needs_fsdp(cfg), mesh=mesh)
    axis = _sync_axis(tcfg.grad_sync)

    def stacked(spec: P) -> P:
        inner = shr.strip_axes(spec, {axis}) if axis else spec
        return P(axis, *tuple(inner))

    if tcfg.grad_sync == "dense":
        cstate: Any = ClientState(u={}, v={}, m={})
        gbar: Any = {}
        srv_spec: Any = {}
        res_spec: Any = {}
    else:
        scheme = resolve(ccfg)
        cstate = ClientState(
            u=tree_map(stacked, pspec) if scheme.uses_u else {},
            v=tree_map(stacked, pspec) if scheme.uses_v else {},
            m=tree_map(stacked, pspec) if scheme.uses_m else {},
        )
        gbar = pspec if scheme.uses_m else {}
        srv_spec = scheme.server_momentum_pspec(pspec)
        # the downlink residual is param-shaped server state: shard it
        # exactly like the params (one copy, laid over the mesh)
        res_spec = scheme.downlink_residual_pspec(pspec)
    return TrainState(
        params=pspec,
        opt=sgd.SGDState(momentum=pspec if tcfg.momentum > 0 else {}),
        cstate=cstate,
        sstate=ServerState(momentum=srv_spec, residual=res_spec),
        gbar=gbar,
        step=P(),
    )


# ---------------------------------------------------------------------------
# Train step
# ---------------------------------------------------------------------------


def _stack_batch(batch, n: int):
    """(B, ...) -> (n, B // n, ...): shard c owns rows [c·B/n, (c+1)·B/n)."""
    def r(x):
        b = x.shape[0]
        if b % n != 0:
            raise ValueError(
                f"global batch {b} must be divisible by the {n} grad-sync shards")
        return x.reshape((n, b // n) + x.shape[1:])
    return tree_map(r, batch)


def _constrain(tree, mesh, spec_fn):
    if mesh is None:
        return tree
    return tree_map(
        lambda x: jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, spec_fn(x))), tree)


def make_train_step(cfg, tcfg, ccfg, mesh=None):
    """Build ``step(state, batch) -> (state, metrics)`` for one grad-sync
    mode. Metrics: loss, upload_nnz (exact int32 per-shard vector — take
    the mean on the host in float64; a device-side float32 mean would
    round above 2^24), download_nnz (the post-downlink broadcast — equals
    the sparse union when the scheme has no downlink stage), total_params
    — the exact wire accounting the launcher turns into MB (see
    ``core.accounting.CostModel``)."""
    sync = tcfg.grad_sync
    # Compressed sync vmaps the loss over sync shards; moe_ep's shard_map
    # under that vmap has no test yet (ROADMAP R3), so EP is only enabled
    # for the dense all-reduce path — gmf_* runs dense experts.
    loss_fn = make_loss_fn(cfg, mesh=mesh if sync == "dense" else None)

    def _apply(params, opt, update, step):
        lr = sgd.lr_at(step, tcfg)
        return sgd.apply_updates(
            params, update, opt, lr=lr, momentum=tcfg.momentum,
            weight_decay=tcfg.weight_decay, grad_clip=tcfg.grad_clip)

    if sync == "dense":

        def step_fn(state: TrainState, batch):
            (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                state.params, batch)
            params, opt = _apply(state.params, state.opt, grads, state.step)
            total = _total_params(state.params)
            metrics = {"loss": loss, "upload_nnz": total,
                       "download_nnz": total, "total_params": total}
            return state._replace(params=params, opt=opt,
                                  step=state.step + 1), metrics

        return step_fn

    axis = _sync_axis(sync)
    n = _num_shards(sync, mesh)
    # Inside a pod the batch stays dense-data-parallel: shard the local
    # batch dim over "data" so the per-pod gradient is a dense all-reduce.
    inner = ("data",) if (sync == "gmf_pod" and mesh is not None
                          and "data" in mesh.axis_names) else ()

    def shard_spec(x):
        return P(axis, inner or None, *([None] * max(x.ndim - 2, 0)))

    scheme = resolve(ccfg)
    if scheme.owns_lr and (tcfg.weight_decay > 0.0 or tcfg.grad_clip > 0.0):
        raise ValueError(
            f"scheme {scheme.name!r} folds the learning rate into its server "
            "update, so optimiser weight_decay/grad_clip would apply to the "
            "lr-scaled update (1/lr times too strong) — set them to 0 for "
            "this scheme")

    def step_fn(state: TrainState, batch):
        sb = _stack_batch(batch, n)
        sb = _constrain(sb, mesh, shard_spec)
        vg = jax.vmap(jax.value_and_grad(loss_fn, has_aux=True),
                      in_axes=(None, 0))
        (losses, _), grads = vg(state.params, sb)
        G, cstate, infos = jax.vmap(
            lambda st, g: scheme.client_compress(st, g, state.gbar, state.step)
        )(state.cstate, grads)
        # the one cross-shard collective: mean of the masked gradients
        g_sum = tree_map(lambda x: jnp.sum(x, axis=0), G)
        lr = sgd.lr_at(state.step, tcfg)
        gbar, sstate, ainfo = scheme.server_aggregate(
            state.sstate, g_sum, float(n), lr=lr, params=state.params)
        if scheme.owns_lr:
            # FetchSGD: lr already entered the sketch-space error feedback —
            # the broadcast is the finished update, applied un-scaled
            # (optimiser momentum composes on the finished updates;
            # weight_decay/grad_clip are rejected at build time below).
            params, opt = sgd.apply_updates(
                state.params, gbar, state.opt, lr=1.0,
                momentum=tcfg.momentum)
        else:
            params, opt = _apply(state.params, state.opt, gbar, state.step)
        new_gbar = gbar if scheme.uses_m else state.gbar
        metrics = {
            "loss": jnp.mean(losses),
            "upload_nnz": infos.upload_nnz,
            "download_nnz": ainfo.download_nnz,
            "total_params": ainfo.total_params,
        }
        return TrainState(params=params, opt=opt, cstate=cstate,
                          sstate=sstate, gbar=new_gbar,
                          step=state.step + 1), metrics

    return step_fn


# ---------------------------------------------------------------------------
# Serving: prefill + decode
# ---------------------------------------------------------------------------


def make_prefill_step(cfg, mesh=None, *, cache_len: int):
    """``prefill(params, batch) -> (last_logits, cache)``.

    Runs the full-sequence forward with ``last_only`` (the (B, T, V) logits
    tensor is never built) and emits the decode cache born-sharded when a
    mesh is given (the cache, not the logits, is the big serving state).
    """
    from repro.models import transformer

    ctx = _model_ctx(cfg, mesh, want_cache=True, cache_len=cache_len,
                     last_only=True)
    if mesh is not None:
        ctx["kv_cache_spec"] = NamedSharding(mesh, shr.kv_entry_spec(cfg, mesh))

    def prefill(params, batch):
        logits, _, cache = transformer.forward(cfg, params, batch, ctx=ctx)
        return logits[..., -1, :].astype(jnp.float32), cache

    return prefill


def make_serve_step(cfg, mesh=None):
    """``serve(params, cache, tokens, pos) -> (next_tokens, logits, cache)``
    — one greedy decode step against the family-specific cache."""
    from repro.models import transformer

    ctx = _model_ctx(cfg, mesh)

    def serve(params, cache, tokens, pos):
        logits, new_cache = transformer.decode_step(
            cfg, params, cache, tokens, pos, ctx=ctx)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return nxt, logits, new_cache

    return serve


# ---------------------------------------------------------------------------
# Serving: paged (continuous-batching) variants
# ---------------------------------------------------------------------------


def make_paged_prefill_step(cfg, codec, mesh=None, *, prompt_pad: int):
    """``prefill(params, tokens, pool, table_row, length) ->
    (next_token, last_logits, pool)`` — admit one request into a slot.

    ``tokens`` is (1, prompt_pad), the prompt right-padded to the fixed
    compile shape (``prompt_pad`` must be a page multiple); ``length`` is
    the true prompt length and ``table_row`` (pages_per_slot,) the slot's
    physical pages. The forward runs ``last_only`` with ``last_index`` so
    only the true last token's logits are built — causal masking keeps the
    padding out of them — and the prompt's K/V pages are scattered into
    the pool with ``codec.write_pages`` (junk K/V beyond ``length`` lands
    in already-owned pages and is masked until decode overwrites it).
    """
    from repro.models import transformer

    ctx_base = _model_ctx(cfg, mesh, want_cache=True, cache_len=prompt_pad,
                          last_only=True)

    def prefill(params, tokens, pool, table_row, length):
        ctx = dict(ctx_base)
        ctx["last_index"] = jnp.reshape(length - 1, (1,))
        logits, _, kv = transformer.forward(
            cfg, params, {"tokens": tokens}, ctx=ctx)
        last = logits[:, 0].astype(jnp.float32)  # (1, V)

        def write_one(pe, ke, ve):
            ps = pe["k"].shape[1]
            n_pages = prompt_pad // ps
            kp = ke[0].reshape(n_pages, ps, *ke.shape[2:])
            vp = ve[0].reshape(n_pages, ps, *ve.shape[2:])
            return codec.write_pages(pe, kp, vp, table_row[:n_pages])

        new_pool = {
            "groups": tuple(
                jax.vmap(write_one)(pe, ce["k"], ce["v"])
                for pe, ce in zip(pool["groups"], kv["groups"], strict=True)),
            "tail": tuple(
                write_one(pe, ce["k"], ce["v"])
                for pe, ce in zip(pool["tail"], kv["tail"], strict=True)),
        }
        nxt = jnp.argmax(last, axis=-1).astype(jnp.int32)
        return nxt, last, new_pool

    return prefill


def make_paged_serve_step(cfg, codec, mesh=None):
    """``serve(params, pool, tables, lengths, tokens) ->
    (next_tokens, logits, pool)`` — one greedy decode step over every
    serving slot at once.

    ``lengths`` (S,) is each slot's current absolute position (prompt
    length + tokens generated so far): the step writes slot i's token at
    position ``lengths[i]`` and attends over positions ≤ it. Inactive
    slots (length 0, table row all scratch) compute garbage that is never
    read back — completion is length bookkeeping on the host, so the
    decode loop stays free of device→host syncs.
    """
    from repro.models import transformer

    ctx = _model_ctx(cfg, mesh)

    def serve(params, pool, tables, lengths, tokens):
        c = dict(ctx, paged={"tables": tables, "codec": codec})
        logits, new_pool = transformer.decode_step(
            cfg, params, pool, tokens, lengths, ctx=c)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return nxt, logits, new_pool

    return serve
