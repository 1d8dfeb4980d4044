"""Mixture-of-Experts FFN (Kimi-K2 / Granite-MoE style: softmax top-k router).

Two implementations sharing one param layout:

* ``moe_dense``  — reference: computes every expert on every token and
  combines with the router weights. Exact (no token dropping), O(E·T·d·f)
  compute — used for smoke tests (E ≤ 4) and the FL simulator.
* ``moe_ep``     — production expert-parallel path for the dry-run meshes.
  Runs inside a ``jax.shard_map`` manual over (data, model):
    - tokens are sharded over ``data`` and replicated over ``model``;
    - expert weights are sharded E→``model`` (EP) and f→``data`` (FSDP);
    - each model rank FSDP-all-gathers its experts' weights, dispatches its
      local tokens that route to its experts through a fixed-capacity
      buffer (sort + local scatter — all local, TPU-friendly), runs the
      grouped GEMMs, combines, and ``psum``s partial outputs over ``model``.
  Compute = top-k · capacity_factor (no 1-hot dispatch tensor is ever
  materialised). Collectives: per-layer weight all-gather (data) + output
  psum (model) — both visible to the roofline pass.

Token dropping: assignments beyond an expert's capacity are dropped (the
standard TPU MoE trade-off); tests check the two paths agree when capacity
is generous enough that nothing drops.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers


def init_moe(key, cfg, dtype=None):
    dtype = dtype or jnp.dtype(cfg.param_dtype)
    kr, k1, k2, k3 = jax.random.split(key, 4)
    e, d, f = cfg.num_experts, cfg.d_model, cfg.d_ff
    return {
        "router": layers.dense_init(kr, d, e, jnp.float32),  # router kept fp32
        "w_gate": layers.truncated_normal_init(k1, (e, d, f), d**-0.5, dtype),
        "w_up": layers.truncated_normal_init(k2, (e, d, f), d**-0.5, dtype),
        "w_down": layers.truncated_normal_init(k3, (e, f, d), f**-0.5, dtype),
    }


def router_topk(params, cfg, x):
    """Route: returns (eids (..., k) int32, gates (..., k), aux_loss scalar)."""
    logits = x.astype(jnp.float32) @ params["router"]  # (..., E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eids = jax.lax.top_k(probs, cfg.experts_per_token)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    # Switch-style load-balance aux loss: E * sum_e f_e * p_e
    e = cfg.num_experts
    density = jnp.mean(
        jax.nn.one_hot(eids, e, dtype=jnp.float32).sum(axis=-2), axis=tuple(range(eids.ndim - 1))
    )  # fraction of tokens hitting each expert (×k)
    mean_prob = jnp.mean(probs, axis=tuple(range(probs.ndim - 1)))
    aux = e * jnp.sum(density / cfg.experts_per_token * mean_prob)
    return eids, gates.astype(x.dtype), aux


def moe_dense(params, cfg, x):
    """Reference path: all experts on all tokens. x: (B, T, d)."""
    b, t, d = x.shape
    xf = x.reshape(b * t, d)
    eids, gates, aux = router_topk(params, cfg, xf)

    def one_expert(w_g, w_u, w_d):
        h = jax.nn.silu(xf @ w_g) * (xf @ w_u)
        return h @ w_d  # (BT, d)

    all_out = jax.vmap(one_expert)(params["w_gate"], params["w_up"], params["w_down"])
    # combine: (E, BT, d) weighted by gate where selected
    combine = jnp.zeros((b * t, cfg.num_experts), x.dtype)
    combine = jnp.sum(
        jax.nn.one_hot(eids, cfg.num_experts, dtype=x.dtype) * gates[..., None], axis=-2
    )  # (BT, E)
    y = jnp.einsum("ebd,be->bd", all_out, combine)
    return y.reshape(b, t, d), aux


# ---------------------------------------------------------------------------
# Expert-parallel path
# ---------------------------------------------------------------------------


def capacity_per_expert(tokens: int, cfg) -> int:
    """Fixed per-expert buffer length (local to one model rank's dispatch)."""
    mean = tokens * cfg.experts_per_token / cfg.num_experts
    return max(1, int(mean * cfg.capacity_factor + 0.999))


def dispatch_local(x, eids, gates, e_base, e_loc, capacity):
    """Build the (e_loc, capacity, d) buffer for this rank's experts from
    local tokens. Pure/local (no collectives) → unit-testable.

    x: (Tl, d); eids/gates: (Tl, k). Returns (buf, tok_idx, pos, keep, le)
    where the index arrays let the caller combine outputs back.
    """
    tl, k = eids.shape
    flat_e = eids.reshape(-1)
    flat_g = gates.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(tl), k)
    le = flat_e - e_base
    hit = (le >= 0) & (le < e_loc)
    # Sort all TK assignments by (miss, local_expert) so this rank's tokens
    # group into contiguous runs; misses sort to the back.
    sort_key = jnp.where(hit, le, e_loc)
    order = jnp.argsort(sort_key, stable=True)
    le_s = jnp.where(hit, le, e_loc)[order]
    tok_s = flat_t[order]
    gate_s = flat_g[order]
    hit_s = hit[order]
    # Position of each assignment within its expert run.
    seg_start = jnp.searchsorted(le_s, jnp.arange(e_loc + 1), side="left")
    pos = jnp.arange(tl * k) - seg_start[jnp.clip(le_s, 0, e_loc)]
    keep = hit_s & (pos < capacity)
    # Scatter into buffer; dropped rows land in a sacrificial extra slot.
    e_idx = jnp.where(keep, le_s, e_loc)
    p_idx = jnp.where(keep, pos, 0)
    buf = jnp.zeros((e_loc + 1, capacity, x.shape[-1]), x.dtype)
    buf = buf.at[e_idx, p_idx].add(jnp.where(keep[:, None], x[tok_s], 0))
    return buf[:e_loc], tok_s, p_idx, keep, e_idx, gate_s


def combine_local(y_buf, tok_s, p_idx, keep, e_idx, gate_s, tl):
    """Gather expert outputs back to token order and gate-weight them."""
    e_loc, _, d = y_buf.shape
    y_pad = jnp.concatenate([y_buf, jnp.zeros_like(y_buf[:1])], axis=0)
    rows = y_pad[e_idx, p_idx]  # (TK, d)
    rows = jnp.where(keep[:, None], rows, 0) * gate_s[:, None].astype(y_buf.dtype)
    out = jnp.zeros((tl, d), y_buf.dtype)
    return out.at[tok_s].add(rows)


def moe_ep_a2a_body(params_loc, cfg, x_loc, *, model_axis: str, fsdp_axis: str | None,
                    n_model: int):
    """All-to-all expert parallelism (DeepSeek/Kimi-style; the production
    path for big-E MoE):

    Tokens arrive *sequence-sharded over the model axis* (16× fewer rows
    per rank than the psum variant), each rank routes its own tokens to
    ALL global experts through a per-source capacity buffer, one
    ``all_to_all`` ships each expert's rows to its owner, local grouped
    GEMMs run, and a reverse ``all_to_all`` returns the outputs. The
    transient (TK, d) dispatch matrix is n_model× smaller than in the
    psum variant — measured on kimi-k2 train_4k this cut per-chip temps
    from 107 GB to the tens (EXPERIMENTS.md §Perf)."""
    bl, tl, d = x_loc.shape
    xf = x_loc.reshape(bl * tl, d)
    eids, gates, aux = router_topk(params_loc, cfg, xf)

    w_g, w_u, w_d = params_loc["w_gate"], params_loc["w_up"], params_loc["w_down"]
    if fsdp_axis is not None:
        w_g = jax.lax.all_gather(w_g, fsdp_axis, axis=2, tiled=True)
        w_u = jax.lax.all_gather(w_u, fsdp_axis, axis=2, tiled=True)
        w_d = jax.lax.all_gather(w_d, fsdp_axis, axis=1, tiled=True)

    e = cfg.num_experts
    cap = capacity_per_expert(bl * tl, cfg)
    # route MY tokens to ALL experts (e_base=0, e_loc=E), then exchange
    buf, tok_s, p_idx, keep, e_idx, gate_s = dispatch_local(xf, eids, gates, 0, e, cap)
    buf = jax.lax.all_to_all(buf, model_axis, split_axis=0, concat_axis=1, tiled=True)
    # buf: (E/n_model, n_model*cap, d) — rows for MY experts from every rank
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w_g)) * jnp.einsum(
        "ecd,edf->ecf", buf, w_u
    )
    y_buf = jnp.einsum("ecf,efd->ecd", h, w_d)
    y_buf = jax.lax.all_to_all(y_buf, model_axis, split_axis=1, concat_axis=0, tiled=True)
    y = combine_local(y_buf, tok_s, p_idx, keep, e_idx, gate_s, bl * tl)
    aux = jax.lax.pmean(aux, model_axis)
    return y.reshape(bl, tl, d), aux


def moe_ep_body(params_loc, cfg, x_loc, rank, *, model_axis: str, fsdp_axis: str | None):
    """Shard-map body: x_loc (Bl, T, d) local tokens; params_loc holds this
    rank's expert shards. ``rank`` is a (1,) int32 carrying this shard's
    model-axis index (passed as a P(model)-sharded iota rather than
    ``axis_index`` — Shardy rejects axis_index inside nested manual
    regions). Call inside shard_map(manual ⊇ {model})."""
    bl, t, d = x_loc.shape
    xf = x_loc.reshape(bl * t, d)
    eids, gates, aux = router_topk(params_loc, cfg, xf)

    w_g, w_u, w_d = params_loc["w_gate"], params_loc["w_up"], params_loc["w_down"]
    if fsdp_axis is not None:
        # FSDP transient gather of this layer's expert weights (f-dim sharded).
        w_g = jax.lax.all_gather(w_g, fsdp_axis, axis=2, tiled=True)
        w_u = jax.lax.all_gather(w_u, fsdp_axis, axis=2, tiled=True)
        w_d = jax.lax.all_gather(w_d, fsdp_axis, axis=1, tiled=True)

    e_loc = w_g.shape[0]
    e_base = rank[0] * e_loc
    cap = capacity_per_expert(bl * t, cfg)
    buf, tok_s, p_idx, keep, e_idx, gate_s = dispatch_local(
        xf, eids, gates, e_base, e_loc, cap
    )
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w_g)) * jnp.einsum(
        "ecd,edf->ecf", buf, w_u
    )
    y_buf = jnp.einsum("ecf,efd->ecd", h, w_d)
    y = combine_local(y_buf, tok_s, p_idx, keep, e_idx, gate_s, bl * t)
    y = jax.lax.psum(y, model_axis)
    aux = jax.lax.pmean(aux, model_axis)
    return y.reshape(bl, t, d), aux


def moe_ep(
    params,
    cfg,
    x,
    *,
    mesh,
    data_axes,
    model_axis: str,
    fsdp_weights: bool,
    already_manual=frozenset(),
):
    """Expert-parallel MoE via shard_map. ``data_axes``: mesh axes the batch
    is sharded over; ``model_axis``: EP axis. ``fsdp_weights``: expert f-dim
    sharded over data_axes[-1] (big archs).

    ``already_manual``: axes made Manual by an *enclosing* shard_map (the
    compressed grad-sync region). Those are dropped from this call's specs
    and axis_names — their collectives still resolve because the outer
    binding is in scope — and the context mesh is used instead of ``mesh``.
    """
    from jax.sharding import PartitionSpec as P

    already_manual = frozenset(already_manual)
    fsdp_axis = data_axes[-1] if fsdp_weights else None
    if fsdp_axis is not None and fsdp_axis in already_manual:
        raise ValueError("FSDP expert sharding cannot use an axis that the "
                         "compressed grad-sync already made manual")

    def vis(axis):
        return axis if (axis is not None and axis not in already_manual) else None

    w_spec_gu = P(vis(model_axis), None, vis(fsdp_axis))
    w_spec_d = P(vis(model_axis), vis(fsdp_axis), None)
    x_dp = tuple(a for a in data_axes if a not in already_manual)
    n_model = mesh.shape[model_axis]
    w_specs = {"router": P(), "w_gate": w_spec_gu, "w_up": w_spec_gu, "w_down": w_spec_d}

    manual = (set(data_axes) | {model_axis}) - already_manual
    # Collectives inside this region may only name axes *this* shard_map
    # binds (Shardy forbids nested regions touching parent-bound axes);
    # the per-outer-shard aux is averaged by the caller's metrics pmean.
    inner_data = tuple(a for a in data_axes if a in manual)

    seq_len = x.shape[1]
    use_a2a = (seq_len % n_model == 0) and (cfg.num_experts % n_model == 0)

    if use_a2a:
        # sequence-sharded dispatch + all_to_all exchange (training/prefill)
        x_spec = P(x_dp or None, model_axis, None)

        def body(p_loc, x_loc):
            y, aux = moe_ep_a2a_body(
                p_loc, cfg, x_loc,
                model_axis=model_axis, fsdp_axis=fsdp_axis, n_model=n_model,
            )
            if inner_data:
                aux = jax.lax.pmean(aux, inner_data)
            return y, aux

        return jax.shard_map(
            body,
            mesh=None if already_manual else mesh,
            in_specs=(w_specs, x_spec),
            out_specs=(x_spec, P()),
            axis_names=manual,
            check_vma=False,
        )(params, x)

    # replicated-token + psum-combine fallback (decode: T == 1)
    x_spec = P(x_dp or None, None, None)
    ranks = jnp.arange(n_model, dtype=jnp.int32)

    def body(p_loc, x_loc, rank):
        y, aux = moe_ep_body(
            p_loc, cfg, x_loc, rank, model_axis=model_axis, fsdp_axis=fsdp_axis
        )
        if inner_data:
            aux = jax.lax.pmean(aux, inner_data)
        return y, aux

    return jax.shard_map(
        body,
        mesh=None if already_manual else mesh,
        in_specs=(w_specs, x_spec, P(model_axis)),
        out_specs=(x_spec, P()),
        axis_names=manual,
        check_vma=False,
    )(params, x, ranks)
