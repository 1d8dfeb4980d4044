"""Composable compression-scheme stages.

A compression scheme is assembled from eight orthogonal stages, each a
small stateless singleton of pure functions (all mutable quantities live
in the ``ClientState``/``ServerState`` pytrees that flow through them, so
a composed scheme is vmap/shard_map/scan-compatible exactly like the old
monolithic branches were):

``selector``     which coordinates are transmitted — ``topk`` (magnitude,
                 exact or DGC-sampled threshold, per-tensor or global),
                 ``randomk`` (rate-sized random coordinate set), ``dense``
                 (everything), ``sketch`` (fixed-size count sketch; the
                 FetchSGD upload — replaces the mask pipeline entirely).
``compensator``  what happens to the un-transmitted residual — ``none``,
                 ``ef`` (error feedback: V accumulates, masked-out entries
                 survive to the next round), ``dgc`` (momentum correction
                 U ← αU + g; V ← V + U, then error feedback).
``fusion``       where the *global* momentum enters — ``none``, ``gmc``
                 (into the compensation: V accumulates g + µM), ``gmf``
                 (into the mask *selection*: the paper's Global Momentum
                 Fusion score, with τ schedule and optional FedNova
                 weighting), ``server_gm`` (server-side momentum on the
                 broadcast — the DGCwGM baseline, paper problem 2.1).
``wire``         payload encoding of the transmitted values — ``float32``
                 (identity), ``float16``/``bfloat16`` (cast), ``int8``
                 (symmetric per-256-block scales, Konečný et al.
                 arXiv:1610.05492), ``probquant`` (the same paper's
                 probabilistic ternary codec: unbiased stochastic keep,
                 ~2 bits/value, per-round PRNG-keyed); the encoding
                 residual G − wire(G) folds back into the error-feedback
                 V so compensation stays exact. Each codec owns the
                 value-bytes term of the communication cost model, and
                 its ``roundtrip`` is reused verbatim by the serving
                 tier's compressed KV cache (`serve/cache.py`).
``rotation``     randomised pre-transform of the payload before the wire
                 codec (1610.05492's "structured random rotation") —
                 ``none`` (identity, today's behaviour) or ``hadamard``
                 (per-round-keyed randomised Hadamard transform H·D/√m:
                 flattens each leaf, pads to a power of two, multiplies
                 by a ±1 diagonal and the fast Walsh–Hadamard butterfly).
                 Rotation spreads outliers across coordinates so the
                 block quantisers see near-Gaussian inputs; the inverse
                 is applied before the residual fold, so the EF state
                 still lives in the original coordinate system. In a real
                 deployment the *rotated* payload crosses the wire and
                 the server applies R⁻¹ after summing (the transform is
                 linear, so server-side inversion of the sum equals the
                 sum of per-client inversions); the simulation folds the
                 inverse into the client-side round trip — the same
                 convention every wire codec here uses. Rotation
                 densifies the payload, so the accounting charges the
                 padded dense size.
``downlink``     compression of the server→client *broadcast* — ``none``
                 (ship the raw aggregate; today's behaviour, bit-exact) or
                 ``topk`` (top-k of the broadcast with a *server-side*
                 residual accumulator, so entries dropped this round are
                 error-fed into the next one — CFedAvg-style). This is the
                 first stage whose state lives on the server side of the
                 protocol (``ServerState.residual``); its payload is
                 wire-encoded like the uplink (rounding error folds back
                 into the residual) and its nnz is what the download term
                 of the cost model charges.
``staleness``    how the server weights a payload that arrives *late* (the
                 asynchronous buffered engine, ``FLConfig.backend="async"``)
                 — ``none`` (weight 1; synchronous semantics), ``poly``
                 (polynomial damping w(s) = (1+s)^(−staleness_exponent)
                 with the gap clipped to ``staleness_horizon``, the FedBuff
                 weighting), ``gmf_damp`` (the GMF-native policy: the
                 payload is poly-damped and the *server-held global
                 momentum* fills in the lost mass, scaled by the staleness
                 gap — stale deltas are steered along the direction the
                 cohort as a whole is moving). All three are exactly the
                 identity at gap 0, which is what makes the async engine
                 bitwise-comparable to the synchronous ones.
``rate_control`` how each sampled client's *effective* compression rate
                 (and wire dtype) is set per round — ``fixed`` (every
                 client at ``cfg.rate``; the engines skip rate threading
                 entirely, bitwise today's behaviour) or ``adaptive``
                 (CFedAvg-style signal feedback — see
                 ``repro.core.rate_control``, where both policies live).

Stages are looked up by name in ``REGISTRY`` (see ``register``); presets
composing them into named schemes live in ``repro.core.registry``.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import fusion as fusion_math
from repro.core import sparsify
from repro.core.state import ClientState
from repro.utils import tree_map, tree_nnz

STAGE_KINDS = ("selector", "compensator", "fusion", "wire", "rotation",
               "downlink", "staleness", "rate_control")

REGISTRY: dict[str, dict[str, Any]] = {kind: {} for kind in STAGE_KINDS}


def register(kind: str, name: str, *, override: bool = False):
    """Class decorator: instantiate the stage and register the singleton.

    Name collisions raise unless ``override=True`` — silently replacing a
    stage another module already registered (and that resolved Schemes may
    already be bound to) is never what a second registration meant.
    """
    if kind not in REGISTRY:
        raise ValueError(
            f"unknown stage kind {kind!r}; choose from {STAGE_KINDS}")

    def deco(cls):
        if name in REGISTRY[kind] and not override:
            raise ValueError(
                f"{kind} stage {name!r} is already registered "
                f"({type(REGISTRY[kind][name]).__name__}); pass "
                f"register({kind!r}, {name!r}, override=True) to replace it")
        obj = cls()
        obj.name = name
        REGISTRY[kind][name] = obj
        return cls

    return deco


def get_stage(kind: str, name: str):
    try:
        return REGISTRY[kind][name]
    except KeyError:
        raise ValueError(
            f"unknown {kind} stage {name!r}; registered {kind}s: "
            f"{tuple(REGISTRY[kind])}"
        ) from None


def available(kind: str) -> tuple[str, ...]:
    return tuple(REGISTRY[kind])


class CompressInfo(NamedTuple):
    """Per-client accounting emitted by client_compress (traced scalars)."""

    upload_nnz: jax.Array      # entries actually transmitted by this client
    total_params: jax.Array    # denominator for density reporting


class AggregateInfo(NamedTuple):
    download_nnz: jax.Array    # entries in the broadcast tensor, AFTER the
                               # downlink stage (what the wire carries — the
                               # download term of the cost model)
    total_params: jax.Array
    union_nnz: Any = None      # pre-downlink union nnz of the aggregate —
                               # the mask-overlap signal the adaptive-tau
                               # controller consumes (None only when a
                               # caller constructs the info by hand)


class StageCtx(NamedTuple):
    """Per-round inputs threaded through the stages (all trace-safe).

    The three trailing fields are rate-control extras and default to
    ``None`` (the fixed-controller path never constructs them, so legacy
    jaxprs are unchanged): ``rate`` is this client's traced effective
    compression rate, ``wire_level`` its traced wire-dtype level (0 = the
    scheme's codec, 1 = drop to int8 for the round), and ``client_id`` the
    client's global id — threaded only for *stochastic* wire codecs so
    each vmapped client draws an independent PRNG stream.
    """

    round_idx: Any
    gbar_prev: Any
    local_steps: Any
    mean_steps: Any
    tau_override: Any
    rate: Any = None
    wire_level: Any = None
    client_id: Any = None


def elementwise_ops(cfg):
    """Elementwise hot-path ops — Pallas-fused or pure-jnp reference."""
    if cfg.use_kernels:
        from repro.kernels import ops as kops

        return kops
    from repro.kernels import ref as kref

    return kref


def effective_tau(cfg, round_idx) -> jax.Array:
    if cfg.tau_warmup_rounds > 0:
        return fusion_math.tau_schedule(round_idx, cfg.tau, cfg.tau_warmup_rounds)
    return jnp.asarray(cfg.tau, jnp.float32)


# ---------------------------------------------------------------------------
# Selectors
# ---------------------------------------------------------------------------


# The top-k's own scope, inside ``round.client_compress`` (fl/engine.py), so
# the selection's device time can be told apart from momentum, fusion and
# error feedback; also wraps the exact top-k of the fused kernel path.
SELECT_SCOPE = "compress.select"


class Selector:
    """Chooses the transmitted coordinate set.

    ``select`` returns a {0,1} mask pytree, or ``None`` for dense
    transmission. ``needs_scores=True`` selectors receive the fusion-shaped
    score tree; the others receive the raw value tree (and must not depend
    on its magnitudes beyond shape).
    """

    needs_scores = True
    dense = False
    sketch = False
    description = ""

    def select(self, cfg, ref_tree, round_idx, rate=None):
        """``rate=None`` (the default) selects at the static ``cfg.rate``;
        a traced per-client rate from the adaptive controller gives the
        magnitude selectors a traced k, which the same threshold search
        takes (see ``sparsify.num_keep_dynamic`` for the bitwise
        relationship between the two k)."""
        raise NotImplementedError


def topk_tree(cfg, scores, rate):
    """{0,1} mask tree of the magnitude top-k of ``scores`` at ``rate``
    (static or traced): per tensor (one threshold search per group of
    same-size leaves) or global, as ``cfg.per_tensor`` says."""
    leaves, treedef = jax.tree_util.tree_flatten(scores)
    if cfg.per_tensor:
        masks = sparsify.topk_masks(leaves, rate, cfg.selector)
    else:
        masks = sparsify.global_topk_masks(leaves, rate)
    return jax.tree_util.tree_unflatten(treedef, masks)


@register("selector", "topk")
class TopKSelector(Selector):
    description = ("magnitude top-k of the (fusion-shaped) score; threshold "
                   "estimator from cfg.selector (exact | sampled), per-tensor "
                   "or global via cfg.per_tensor")

    def select(self, cfg, scores, round_idx, rate=None):
        with jax.named_scope(SELECT_SCOPE):
            return topk_tree(cfg, scores, cfg.rate if rate is None else rate)


@register("selector", "dense")
class DenseSelector(Selector):
    needs_scores = False
    dense = True
    description = "no sparsification — every entry is transmitted"

    def select(self, cfg, value, round_idx, rate=None):
        return None


@register("selector", "randomk")
class RandomKSelector(Selector):
    needs_scores = False
    description = ("rate-sized random coordinate set per round (no magnitude "
                   "information — the ablation baseline)")

    def select(self, cfg, value, round_idx, rate=None):
        r = cfg.rate if rate is None else rate
        key = jax.random.PRNGKey(17)
        key = jax.random.fold_in(key, jnp.asarray(round_idx, jnp.int32))
        leaves, treedef = jax.tree_util.tree_flatten(value)
        masks_l = [
            (
                jax.random.uniform(jax.random.fold_in(key, i), x.shape) < r
            ).astype(jnp.float32)
            for i, x in enumerate(leaves)
        ]
        return jax.tree_util.tree_unflatten(treedef, masks_l)


@register("selector", "sketch")
class SketchSelector(Selector):
    sketch = True
    needs_scores = False
    description = ("fixed-size count sketch of the whole gradient (FetchSGD "
                   "upload); server keeps momentum + error feedback in sketch "
                   "space and broadcasts k heavy hitters")

    def select(self, cfg, value, round_idx, rate=None):  # pragma: no cover
        raise RuntimeError("sketch selector replaces the mask pipeline; "
                           "handled by Scheme directly")


# ---------------------------------------------------------------------------
# Compensators
# ---------------------------------------------------------------------------


class Compensator:
    """Accumulates gradients into the client memory and extracts the
    transmitted values against a mask.

    ``accumulate(cfg, ops, u, v, grad, extra) -> (value, u, v)`` where
    ``extra`` is an optional pytree injected by the fusion stage (GMC's µM
    term) and ``value`` is the tensor the transmitted entries are read from.
    ``extract(cfg, ops, u, v, value, masks) -> (g_out, u, v)`` applies the
    mask (``None`` = dense) and clears transmitted entries from the memory.
    """

    uses_u = False
    uses_v = False
    description = ""

    def accumulate(self, cfg, ops, u, v, grad, extra):
        raise NotImplementedError

    def extract(self, cfg, ops, u, v, value, masks):
        raise NotImplementedError


@register("compensator", "none")
class NoCompensation(Compensator):
    description = "masked-out entries are dropped (plain top-k / FedSGD)"

    def accumulate(self, cfg, ops, u, v, grad, extra):
        value = grad if extra is None else tree_map(lambda g, e: g + e, grad, extra)
        return value, u, v

    def extract(self, cfg, ops, u, v, value, masks):
        g_out = value if masks is None else tree_map(jnp.multiply, value, masks)
        return g_out, u, v


@register("compensator", "ef")
class ErrorFeedback(Compensator):
    uses_v = True
    description = "error feedback: V accumulates everything; masked-out " \
                  "entries survive in V to the next round"

    def accumulate(self, cfg, ops, u, v, grad, extra):
        if extra is None:
            v = tree_map(jnp.add, v, grad)
        else:
            v = tree_map(lambda vv, g, e: vv + g + e, v, grad, extra)
        return v, u, v

    def extract(self, cfg, ops, u, v, value, masks):
        if masks is None:
            return v, u, tree_map(lambda vv: vv * 0.0, v)
        g_out = tree_map(jnp.multiply, v, masks)
        v = tree_map(lambda vv, mk: vv * (1.0 - mk), v, masks)
        return g_out, u, v


@register("compensator", "dgc")
class MomentumCorrection(Compensator):
    uses_u = True
    uses_v = True
    description = "DGC momentum correction (U ← αU + g; V ← V + U) on top " \
                  "of error feedback"

    def accumulate(self, cfg, ops, u, v, grad, extra):
        g_eff = grad if extra is None else tree_map(lambda g, e: g + e, grad, extra)
        u, v = ops.momentum_correction(u, v, g_eff, cfg.alpha)
        return v, u, v

    def extract(self, cfg, ops, u, v, value, masks):
        if masks is None:
            zeros = lambda t: tree_map(lambda x: x * 0.0, t)
            return v, zeros(u), zeros(v)
        return ops.apply_mask_update(u, v, masks)


# ---------------------------------------------------------------------------
# Fusions
# ---------------------------------------------------------------------------


class Fusion:
    """Where the accumulated *global* momentum enters the scheme.

    Client side: ``pre`` runs before the compensator (may update M and
    inject an extra accumulation term), ``scores`` runs after it (may update
    M and reshape the selection score). Server side: ``server`` transforms
    the averaged aggregate into the broadcast (server momentum lives here).
    """

    uses_m = False
    server_momentum = False
    description = ""

    def pre(self, cfg, m, gbar_prev):
        return m, None

    def scores(self, cfg, value, m, ctx: StageCtx):
        return tree_map(jnp.abs, value), m

    def server(self, cfg, momentum, gbar):
        """(broadcast, new server momentum) from the averaged aggregate."""
        return gbar, momentum


@register("fusion", "none")
class NoFusion(Fusion):
    description = "no global momentum; score = |value|"


@register("fusion", "gmc")
class GlobalMomentumCompensation(Fusion):
    uses_m = True
    description = ("GMC: global momentum in the *compensation* — M ← µM + Ĝ "
                   "and V accumulates g + µM; score stays |V|")

    def pre(self, cfg, m, gbar_prev):
        m = tree_map(lambda mm, gb: cfg.mu * mm + gb, m, gbar_prev)
        extra = tree_map(lambda mm: cfg.mu * mm, m)
        return m, extra


@register("fusion", "server_gm")
class ServerGlobalMomentum(Fusion):
    server_momentum = True
    description = ("server-side global momentum on the broadcast (DGCwGM; "
                   "paper problem 2.1 — the download densifies)")

    def server(self, cfg, momentum, gbar):
        mom = tree_map(lambda m, g: cfg.beta_server * m + g, momentum, gbar)
        return mom, mom


@register("fusion", "gmf")
class GlobalMomentumFusion(Fusion):
    uses_m = True
    description = ("the paper's GMF: M ← βM + Ĝ and the selection score is "
                   "|(1−τ)·w·N(V) + τ·N(M)| (τ schedule via "
                   "tau_warmup_rounds, w via fusion_weighting=fednova)")

    def _tau_w(self, cfg, ctx: StageCtx):
        tau = (ctx.tau_override if ctx.tau_override is not None
               else effective_tau(cfg, ctx.round_idx))
        if cfg.fusion_weighting == "fednova":
            w = fusion_math.fednova_step_weight(ctx.local_steps, ctx.mean_steps)
        else:
            w = jnp.asarray(1.0, jnp.float32)
        return tau, w

    def scores(self, cfg, value, m, ctx: StageCtx):
        m = tree_map(lambda mm, gb: cfg.beta * mm + gb, m, ctx.gbar_prev)
        tau, w = self._tau_w(cfg, ctx)
        scores = tree_map(
            lambda vv, mm: jnp.abs(
                (1.0 - tau) * w * fusion_math.l2_normalize(vv, cfg.eps)
                + tau * fusion_math.l2_normalize(mm, cfg.eps)
            ),
            value,
            m,
        )
        return scores, m

    def fused_compress(self, cfg, u, v, m, ctx: StageCtx):
        """Alternate implementation of score+mask+extract through the fused
        Pallas kernels (``kernels/gmf_compress.py``): per-leaf scalar norms +
        threshold are computed outside, then one VMEM pass produces
        (G, U', V', mask). Returns (g, u, v, m, masks).

        Under the exact selector the mask is set from the top-k's own
        indices, exactly k per leaf, and the kernel only applies it. A
        score recomputed elsewhere (inside the kernel, or in another XLA
        fusion) can round the k-th element itself below the threshold and
        send fewer than k entries. The sampled selector scores inside the
        kernel (one pass).

        Numerically equivalent to ``scores``+topk+``extract`` up to
        reciprocal-vs-division rounding in the normalisation (boundary ties
        in the mask can differ); selected only under ``use_kernels``.
        """
        from repro.kernels import ops as kops
        from repro.kernels.ref import _multimap

        m = tree_map(lambda mm, gb: cfg.beta * mm + gb, m, ctx.gbar_prev)
        tau, w = self._tau_w(cfg, ctx)

        def leaf(u_, v_, m_):
            vf = v_.astype(jnp.float32)
            mf = m_.astype(jnp.float32)
            # w folds into V's inverse norm: (1−τ)·w·N(V) = (1−τ)·V·(w/‖V‖)
            inv_nv = w / (jnp.sqrt(jnp.sum(jnp.square(vf))) + cfg.eps)
            inv_nm = 1.0 / (jnp.sqrt(jnp.sum(jnp.square(mf))) + cfg.eps)
            if cfg.selector == "exact":
                z = jnp.abs((1.0 - tau) * vf * inv_nv + tau * mf * inv_nm)
                with jax.named_scope(SELECT_SCOPE):
                    _, idx = jax.lax.top_k(
                        z.reshape(-1), sparsify.num_keep(v_.size, cfg.rate))
                    mask = jnp.zeros(v_.size, v_.dtype).at[idx].set(
                        1, unique_indices=True).reshape(v_.shape)
                return (*kops.apply_mask_update(u_, v_, mask), mask)
            vs = sparsify.strided_sample_nd(vf)
            ms = sparsify.strided_sample_nd(mf)
            zs = jnp.abs((1.0 - tau) * vs * inv_nv + tau * ms * inv_nm)
            k = sparsify.num_keep(zs.shape[0], cfg.rate)
            with jax.named_scope(SELECT_SCOPE):
                thr = sparsify.exact_threshold(zs, k)
            return kops.gmf_compress(
                u_, v_, m_, inv_norm_v=inv_nv, inv_norm_m=inv_nm, tau=tau,
                threshold=thr)

        g, u, v, masks = _multimap(leaf, 4, u, v, m)
        return g, u, v, m, masks


# ---------------------------------------------------------------------------
# Wire codecs
# ---------------------------------------------------------------------------


class WireCodec:
    """Encoding of the transmitted values. ``value_bytes`` feeds the
    communication cost model; ``encode`` may fold encoding error back into
    the client state (quantisation-aware error feedback). ``roundtrip`` is
    the pure encode→decode map on one tensor — the downlink stage reuses it
    for the broadcast payload, and the serving tier's compressed KV cache
    uses the same codecs (`serve/cache.py`).

    ``stochastic = True`` codecs draw PRNG randomness per round trip;
    ``roundtrip_ctx`` lets them key the draw from the :class:`StageCtx`
    (round / leaf / client), so independent clients in one vmapped round
    get independent noise. Deterministic codecs ignore the context — their
    ``roundtrip_ctx`` just forwards to ``roundtrip``.
    """

    value_bytes: float = 4
    dtype = "float32"
    stochastic = False
    description = ""

    def roundtrip(self, x):
        """What a tensor looks like after crossing the wire (identity for
        float32; cast for the 16-bit codecs; quantise+dequantise for
        ``int8``). Pure — the caller owns any error feedback."""
        return x

    def roundtrip_ctx(self, cfg, x, ctx: StageCtx | None, leaf_idx: int = 0):
        """Context-aware round trip (stochastic codecs key their PRNG from
        ``ctx``; deterministic codecs ignore it)."""
        return self.roundtrip(x)

    def roundtrip_tree(self, cfg, tree, ctx: StageCtx | None = None):
        """Round-trip a whole pytree, giving each leaf its own key slot."""
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        out = [self.roundtrip_ctx(cfg, x, ctx, i) for i, x in enumerate(leaves)]
        return jax.tree_util.tree_unflatten(treedef, out)

    def encode(self, cfg, g_out, state: ClientState, ctx: StageCtx | None = None):
        return g_out, state


@register("wire", "float32")
class Float32Wire(WireCodec):
    description = "full-precision payload (identity)"


class _RoundtripFoldWire(WireCodec):
    """Send the payload through ``roundtrip``; the encoding residual
    (G − wire(G)) folds back into the error-feedback state V so nothing is
    lost — the next round re-compensates it. Schemes without V transmit the
    plain round-tripped payload."""

    def encode(self, cfg, g_out, state: ClientState, ctx: StageCtx | None = None):
        g_wire = self.roundtrip_tree(cfg, g_out, ctx)
        v = state.v
        if jax.tree_util.tree_leaves(v):
            v = tree_map(lambda vv, g, gw: vv + (g - gw), v, g_out, g_wire)
        return g_wire, ClientState(u=state.u, v=v, m=state.m)


class _CastFoldWire(_RoundtripFoldWire):
    dtype = "float32"
    value_bytes = 2

    def roundtrip(self, x):
        return x.astype(jnp.dtype(self.dtype)).astype(x.dtype)


@register("wire", "float16")
class Float16Wire(_CastFoldWire):
    dtype = "float16"
    description = "fp16 payload; quantisation residual folds into V"


@register("wire", "bfloat16")
class BFloat16Wire(_CastFoldWire):
    dtype = "bfloat16"
    description = "bf16 payload; quantisation residual folds into V"


@register("wire", "int8")
class Int8Wire(_RoundtripFoldWire):
    """Symmetric int8 with one fp32 scale per 256-entry flat block
    (`utils/quant.py`); the quantisation residual folds into V like the
    16-bit casts. ``value_bytes`` charges 1 byte/value — the per-block
    scale adds 4/256 byte/value, well under the cost model's 4-byte index
    term for sparse payloads. All-zero blocks decode to exact zeros, so
    sparsity (and the nnz accounting) survives the round trip. The same
    codec quantises the paged KV cache (`serve/cache.py`)."""

    dtype = "int8"
    value_bytes = 1
    description = ("int8 payload, per-256-block symmetric scales; "
                   "quantisation residual folds into V (grad-sync and "
                   "KV-cache share the codec)")

    def roundtrip(self, x):
        from repro.utils.quant import roundtrip_q8_blocks

        return roundtrip_q8_blocks(x)


@register("wire", "probquant")
class ProbQuantWire(_RoundtripFoldWire):
    """Probabilistic ternary codec (Konečný et al., arXiv:1610.05492 §3):
    per 256-entry flat block each value ships as ``sign(x)·amax`` with
    probability ``|x|/amax`` and as 0 otherwise, so the round trip is
    unbiased — ``E[x̂] = x`` — and the zero-mean rounding noise folds into
    V like every other wire residual. A transmitted entry is one of
    {−s, 0, +s}, so ~2 bits of payload per value; ``value_bytes = 0.25``
    (the per-block fp32 scale adds 4/256 byte/value on top, same as int8).

    The keep/drop draw is keyed ``probquant_seed → round → leaf → client``
    so every (round, leaf, client) triple is an independent stream — under
    the client vmap this is what makes the aggregate's noise variance
    shrink as 1/K instead of staying per-client-correlated. When no
    context is available (the downlink reusing the codec, the analysis
    probes) the pure ``roundtrip`` falls back to a fixed key: still a
    valid draw, just not round-decorrelated."""

    dtype = "ternary"
    value_bytes = 0.25
    stochastic = True
    description = ("probabilistic ternary payload (unbiased stochastic "
                   "keep, ~2 bits/value, per-256-block scales); PRNG keyed "
                   "by round/leaf/client, rounding noise folds into V")

    def _key(self, cfg, ctx: StageCtx | None, leaf_idx: int):
        key = jax.random.PRNGKey(cfg.probquant_seed)
        if ctx is not None:
            key = jax.random.fold_in(key, jnp.asarray(ctx.round_idx, jnp.int32))
        key = jax.random.fold_in(key, leaf_idx)
        if ctx is not None and ctx.client_id is not None:
            key = jax.random.fold_in(
                key, jnp.asarray(ctx.client_id, jnp.int32))
        return key

    def roundtrip(self, x):
        from repro.utils.quant import roundtrip_ternary_blocks

        return roundtrip_ternary_blocks(x, jax.random.PRNGKey(0))

    def roundtrip_ctx(self, cfg, x, ctx: StageCtx | None, leaf_idx: int = 0):
        from repro.utils.quant import roundtrip_ternary_blocks

        return roundtrip_ternary_blocks(x, self._key(cfg, ctx, leaf_idx))


# ---------------------------------------------------------------------------
# Rotation (randomised pre-transform ahead of the wire codec)
# ---------------------------------------------------------------------------


class Rotation:
    """Linear, norm-preserving pre-transform applied per leaf before the
    wire codec (and inverted before the error-feedback fold), so block
    quantisers see spread-out, near-Gaussian coordinates instead of raw
    gradient outliers (arXiv:1610.05492 "structured random rotation").

    ``forward(cfg, x, round_idx, leaf_idx)`` flattens one leaf and returns
    the rotated 1-D vector (possibly longer than ``x.size`` — Hadamard
    pads to a power of two); ``inverse(cfg, y, round_idx, like, leaf_idx)``
    undoes it and restores ``like``'s shape/dtype. Both are pure and keyed
    only by static config + the traced round index, so client and server
    agree on R without communicating. ``wire_size(n)`` is the number of
    values that actually cross the wire for an ``n``-element leaf —
    rotation densifies, so this is the padded dense length.

    In a real deployment the *rotated* payload is what ships and the
    server applies R⁻¹ once, after summing — R is linear, so
    ``R⁻¹(Σ y_k) == Σ R⁻¹(y_k)`` and the simulation may instead fold the
    inverse into each client's round trip (`Scheme._encode_payload`),
    which keeps every engine's aggregation path untouched. ``identity =
    True`` rotations are skipped entirely (no jaxpr change)."""

    identity = True
    description = ""

    def forward(self, cfg, x, round_idx, leaf_idx: int = 0):
        return jnp.asarray(x, jnp.float32).reshape(-1)

    def inverse(self, cfg, y, round_idx, like, leaf_idx: int = 0):
        return y[: like.size].reshape(like.shape).astype(like.dtype)

    def wire_size(self, n: int) -> int:
        return n


@register("rotation", "none")
class NoRotation(Rotation):
    description = "identity — payloads hit the wire codec untransformed"


def _fwht(x: jax.Array) -> jax.Array:
    """Fast Walsh–Hadamard transform of a power-of-two-length vector
    (unnormalised butterfly: H·x for the ±1 Sylvester matrix H)."""
    n = x.shape[0]
    h = 1
    while h < n:
        x = x.reshape(-1, 2, h)
        x = jnp.concatenate([x[:, 0] + x[:, 1], x[:, 0] - x[:, 1]], axis=-1)
        h *= 2
    return x.reshape(-1)


@register("rotation", "hadamard")
class HadamardRotation(Rotation):
    identity = False
    description = ("randomised Hadamard transform R = H·D/√m per leaf "
                   "(pad to power of two, ±1 diagonal keyed by "
                   "rotation_seed/round/leaf); orthonormal, so R⁻¹ = "
                   "D·H/√m and norms are preserved")

    def _diag(self, cfg, n: int, round_idx, leaf_idx: int):
        key = jax.random.PRNGKey(cfg.rotation_seed)
        key = jax.random.fold_in(key, jnp.asarray(round_idx, jnp.int32))
        key = jax.random.fold_in(key, leaf_idx)
        return jax.random.rademacher(key, (n,), jnp.float32)

    @staticmethod
    def _padded(n: int) -> int:
        return 1 << max(0, (n - 1).bit_length())

    def forward(self, cfg, x, round_idx, leaf_idx: int = 0):
        flat = jnp.asarray(x, jnp.float32).reshape(-1)
        n = flat.shape[0]
        m = self._padded(n)
        if m != n:
            flat = jnp.concatenate([flat, jnp.zeros((m - n,), jnp.float32)])
        d = self._diag(cfg, m, round_idx, leaf_idx)
        return _fwht(d * flat) / jnp.sqrt(jnp.asarray(m, jnp.float32))

    def inverse(self, cfg, y, round_idx, like, leaf_idx: int = 0):
        m = y.shape[0]
        d = self._diag(cfg, m, round_idx, leaf_idx)
        flat = d * _fwht(y) / jnp.sqrt(jnp.asarray(m, jnp.float32))
        return flat[: like.size].reshape(like.shape).astype(like.dtype)

    def wire_size(self, n: int) -> int:
        return self._padded(n)


# ---------------------------------------------------------------------------
# Downlink (server -> client broadcast compression)
# ---------------------------------------------------------------------------


class Downlink:
    """Compression of the broadcast. ``apply(cfg, wire, residual, bcast,
    nnz)`` -> (broadcast_out, new_residual, download_nnz): the tensor that
    is actually unicast to the K clients, the updated server-side residual
    (``ServerState.residual``) and the post-downlink nnz the download term
    of the cost model charges. ``nnz`` is the pre-downlink nnz of ``bcast``
    (the sparse union), which passthrough stages report unchanged."""

    uses_residual = False
    description = ""

    def apply(self, cfg, wire, residual, bcast, nnz):
        return bcast, residual, nnz


@register("downlink", "none")
class NoDownlink(Downlink):
    description = "broadcast the raw aggregate (hub-and-spoke baseline; " \
                  "bit-exact with the pre-downlink-stage behaviour)"


@register("downlink", "topk")
class TopKDownlink(Downlink):
    uses_residual = True
    description = ("top-k of the broadcast against a server-side residual "
                   "accumulator (error feedback on the downlink, CFedAvg-"
                   "style); rate from cfg.downlink_rate, threshold "
                   "estimator / per-tensor-vs-global from the selector "
                   "knobs, payload wire-encoded like the uplink")

    def apply(self, cfg, wire, residual, bcast, nnz):
        # residual accumulates everything the clients have not seen yet;
        # dropped entries survive to the next round's selection.
        r = tree_map(jnp.add, residual, bcast)
        masks = topk_tree(cfg, r, cfg.downlink_rate)
        # Unlike the uplink's V, the accumulated broadcast is mostly EXACT
        # zeros while the union is sparse — a zero top-k threshold would
        # select everything (|0| >= 0), so zero entries never transmit.
        masks = tree_map(
            lambda mk, z: mk * (z != 0.0).astype(mk.dtype), masks, r)
        out = tree_map(jnp.multiply, r, masks)
        # wire-aware: the broadcast payload ships through the scheme's wire
        # codec (cast for fp16/bf16, block-quantise for int8); the encoding
        # residual (G − wire(G)) folds back into the server residual,
        # mirroring the uplink's quantisation-aware EF. With mk ∈ {0,1}
        # that collapses to residual = accumulated − transmitted:
        # r·(1−mk) + (r·mk − wire(r·mk)) == r − wire(r·mk) elementwise.
        out_w = tree_map(wire.roundtrip, out)
        residual = tree_map(jnp.subtract, r, out_w)
        return out_w, residual, tree_nnz(masks)


# ---------------------------------------------------------------------------
# Staleness (asynchronous buffered aggregation — payload age weighting)
# ---------------------------------------------------------------------------


class Staleness:
    """How the server treats a payload that arrives ``gap`` ticks after the
    model snapshot it was computed against (``gap = t_apply − t_dispatch``).

    ``weight(cfg, gap)`` returns the scalar multiplier on the payload;
    ``combine(cfg, payload, gap, gmom)`` produces the tensor that actually
    enters the buffered aggregate, where ``gmom`` is the *server-held*
    global momentum (an EMA of broadcasts the async engine maintains;
    ``None``/empty for policies that don't use it). Both are pure and
    traced per payload, so the engine vmaps ``combine`` over the buffer
    axis. Every policy must be the exact identity at ``gap == 0`` — that
    invariant is what pins ``backend="async"`` to the synchronous engines
    bitwise at zero delay (tests/test_async.py).

    Gaps are clipped to ``cfg.staleness_horizon`` before weighting, so
    weights are bounded below by ``(1 + horizon)^(−staleness_exponent)``
    and an arbitrarily late payload can never vanish (or, for ``gmf_damp``,
    never be replaced entirely by momentum).
    """

    uses_momentum = False
    description = ""

    def _gap(self, cfg, gap):
        g = jnp.asarray(gap, jnp.float32)
        return jnp.minimum(g, jnp.asarray(float(cfg.staleness_horizon), jnp.float32))

    def weight(self, cfg, gap):
        return jnp.ones_like(jnp.asarray(gap, jnp.float32))

    def combine(self, cfg, payload, gap, gmom):
        w = self.weight(cfg, gap)
        return tree_map(lambda g: w * g, payload)


@register("staleness", "none")
class NoStaleness(Staleness):
    description = ("every payload weighs 1 regardless of age (synchronous "
                   "semantics; the identity — payloads pass through "
                   "untouched)")

    def combine(self, cfg, payload, gap, gmom):
        return payload  # exact identity, bitwise


@register("staleness", "poly")
class PolyStaleness(Staleness):
    description = ("polynomial damping w(s) = (1+s)^(−staleness_exponent), "
                   "gap clipped to staleness_horizon (FedBuff-style); "
                   "exponent 0 == none")

    def weight(self, cfg, gap):
        s = self._gap(cfg, gap)
        return (1.0 + s) ** (-jnp.asarray(cfg.staleness_exponent, jnp.float32))


@register("staleness", "gmf_damp")
class GMFDampStaleness(Staleness):
    uses_momentum = True
    description = ("GMF-native: payload poly-damped by w(s) and the "
                   "server-held global momentum fills the gap — "
                   "w(s)·g + staleness_tau·(1−w(s))·M, identity at s=0 "
                   "(fresh payloads untouched; stale directions are "
                   "steered along the cohort's momentum)")

    def weight(self, cfg, gap):
        s = self._gap(cfg, gap)
        return (1.0 + s) ** (-jnp.asarray(cfg.staleness_exponent, jnp.float32))

    def combine(self, cfg, payload, gap, gmom):
        w = self.weight(cfg, gap)
        lam = jnp.asarray(cfg.staleness_tau, jnp.float32) * (1.0 - w)
        if not jax.tree_util.tree_leaves(gmom):
            return tree_map(lambda g: w * g, payload)
        return tree_map(lambda g, mm: w * g + lam * mm, payload, gmom)
