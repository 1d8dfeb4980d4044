"""Jit-safe top-k mask selection for gradient sparsification.

Two threshold estimators:

* ``exact``   — the k-th largest score itself, by a search over its float32
  bit pattern (:func:`exact_thresholds`): 31 compare-and-count passes over
  the scores, no sort. Exact nnz (every entry tied with the k-th value is
  kept too); the per-tensor path runs one search per group of same-size
  leaves (:func:`select_groups`).
* ``sampled`` — Deep Gradient Compression's estimator: take a strided sample,
  use the k'th largest of the sample as the threshold (the same search, on
  the sample). O(n), TPU-friendly for 10^8+-element tensors. nnz is then
  approximate (property tests bound the error); the accounting layer always
  reports the *actual* nnz of the produced mask.

Both return {0,1} masks of the inputs' shapes, selected from *score* tensors
``z`` (which for plain DGC is ``|v|`` and for GMF is the fusion score) — the
mask is then applied to the *value* tensor by the caller. ``rate`` is a
Python float (static k, :func:`num_keep`) or a traced float32 scalar (the
adaptive rate controller's, :func:`num_keep_dynamic`); the search takes
either k.
"""

from __future__ import annotations

import math
from typing import Literal

import jax
import jax.numpy as jnp

Selector = Literal["exact", "sampled"]

# Sample size target for the DGC sampled estimator.
_SAMPLE_TARGET = 16384


def num_keep(n: int, rate: float) -> int:
    """Number of kept elements for compression rate ``rate`` (static)."""
    if not 0.0 < rate <= 1.0:
        raise ValueError(f"compression rate must be in (0, 1], got {rate}")
    return max(1, min(n, int(math.ceil(rate * n))))


def num_keep_dynamic(n: int, rate) -> jax.Array:
    """Traced-rate sibling of :func:`num_keep` (int32 scalar).

    ``rate`` is a traced float32 scalar (the adaptive rate controller's
    per-client output), so the ceil happens in float32. For dyadic rates
    (0.5, 0.25, …) the product is exact and this matches the static
    ``num_keep`` bit for bit — the flat-signal controller identity tests
    rely on that; for non-dyadic rates the two can differ by the one ulp
    float32 loses over Python's float64 (never more than one element).
    """
    k = jnp.ceil(jnp.asarray(rate, jnp.float32) * n).astype(jnp.int32)
    return jnp.clip(k, 1, n)


def _keep(n: int, rate):
    """k for ``n`` elements: static for a Python rate, traced otherwise."""
    if isinstance(rate, (int, float)):
        return num_keep(n, rate)
    return num_keep_dynamic(n, rate)


# A TPU vector register holds an (8, 128) tile of 32-bit values.
_SUBLANES, _LANES = 8, 128


def _tiled(x: jax.Array) -> jax.Array:
    """``[m, ...]`` int32 bit patterns as ``[m, rows, 128]`` with full TPU
    tiles: each row flattened and, from one tile up, zero-padded to whole
    tiles. A stacked ``[m, n]`` would tile over (m, n), padding every
    m < 8 to 8 sublanes, an 8x read for a single leaf. Zero patterns never
    count: every candidate of the search is at least 1. Rows shorter than a
    tile stay ``[m, n]``."""
    m, n = x.shape[0], math.prod(x.shape[1:])
    x = x.reshape(m, n)
    tile = _SUBLANES * _LANES
    if n < tile:
        return x
    x = jnp.pad(x, ((0, 0), (0, -n % tile)))
    return x.reshape(m, -1, _LANES)


def exact_thresholds(zs: list[jax.Array], ks: list) -> list[jax.Array]:
    """k-th largest value of each row of non-negative float32 arrays.

    Each ``zs[i]`` is ``[m, ...]``: ``m`` independent searches, each over
    all of its row's remaining axes, with ``ks[i]`` (static int or traced
    int32 scalar, 1 <= k <= row size) shared by the rows. Returns ``[m]``
    float32 per array, bit for bit what ``lax.top_k(row, k)[0][-1]`` gives.

    Non-negative float32 values (+0, subnormals and +inf included) order as
    their int32 bit patterns, and the k-th largest value is the largest
    pattern ``t`` with ``count(bits >= t) >= k``. That count only falls as
    ``t`` grows, so ``t`` is built greedily from bit 30 down to bit 0: 31
    passes of one compare-and-count each, no sort. One loop runs the
    searches of every array; its operands are the materialised bit
    patterns, which each pass reads once. (Settling 3 bits a pass, with 7
    counts per read, took longer on a TPU v5e: the counts, not the reads,
    bound a pass.)
    """
    bits = [_tiled(jax.lax.bitcast_convert_type(z, jnp.int32)) for z in zs]

    def step(i, ts):
        out = []
        for x, t, k in zip(bits, ts, ks, strict=True):
            cand = t | jnp.left_shift(jnp.int32(1), 30 - i)
            rows = cand.reshape(cand.shape + (1,) * (x.ndim - 1))
            count = jnp.sum(x >= rows, axis=tuple(range(1, x.ndim)), dtype=jnp.int32)
            out.append(jnp.where(count >= k, cand, t))
        return out

    ts = jax.lax.fori_loop(
        0, 31, step, [jnp.zeros(x.shape[:1], jnp.int32) for x in bits])
    return [jax.lax.bitcast_convert_type(t, jnp.float32) for t in ts]


def exact_threshold(z: jax.Array, k) -> jax.Array:
    """Exact k-th largest value of the non-negative float32 ``z`` (any
    shape; k static or traced)."""
    return exact_thresholds([z[None]], [k])[0][0]


def strided_sample_nd(z: jax.Array, target: int = _SAMPLE_TARGET) -> jax.Array:
    """≈``target``-element strided sample WITHOUT flattening the input.

    Flattening a sharded tensor (`reshape(-1)`) forces an all-gather under
    SPMD — on a 10⁹-element gradient that is gigabytes of traffic per
    round. Multi-dim strided slicing keeps the big tensor sharded; only the
    (tiny) sample is gathered for the top-k. (Measured: this one change
    removed ~15 GB/step of all-gather traffic on llama3.2-1b train_4k —
    EXPERIMENTS.md §Perf iteration 0.)
    """
    total = z.size
    stride_budget = max(1, total // target)
    strides = []
    for d in z.shape:
        s = min(d, stride_budget)
        strides.append(s)
        stride_budget = max(1, stride_budget // s)
    sample = z[tuple(slice(None, None, s) for s in strides)]
    return sample.reshape(-1)


def select_groups(shapes) -> list[list[int]]:
    """The per-tensor selection's plan: leaf indices grouped by element
    count, in order of first appearance. Each group is stacked to
    ``[m, n]`` and searched once."""
    groups: dict[int, list[int]] = {}
    for i, shape in enumerate(shapes):
        groups.setdefault(math.prod(shape), []).append(i)
    return list(groups.values())


def topk_masks(zs: list[jax.Array], rate, selector: Selector = "exact") -> list[jax.Array]:
    """Per-tensor {0,1} float32 masks keeping ~``rate`` of each leaf's
    largest ``|z|``.

    The mask comparison is elementwise on each leaf's own shape. The
    searched rows (the flattened leaf, or its strided sample for
    ``sampled``) are stacked by length, one threshold search per length:
    flat rows keep the TPU's 128-lane tiles full where a leaf's last axis
    is narrow (a 3x3x16x16 convolution would fill an eighth of them).
    """
    if selector not in ("exact", "sampled"):
        raise ValueError(f"unknown selector {selector!r}")
    # Materialised once: the mask compares the very values the search
    # counted (a score recomputed in another fusion can round across the
    # threshold), and the stacking below cannot steer how XLA lays out
    # the code that produces the scores.
    za = jax.lax.optimization_barrier([jnp.abs(z).astype(jnp.float32) for z in zs])
    pick = (lambda z: z) if selector == "exact" else strided_sample_nd
    rows = [pick(z).reshape(-1) for z in za]
    groups = select_groups([r.shape for r in rows])
    stacks = [jnp.stack([rows[i] for i in ix]) for ix in groups]
    thrs = exact_thresholds(stacks, [_keep(s.shape[1], rate) for s in stacks])
    masks = [None] * len(za)
    for ix, thr in zip(groups, thrs, strict=True):
        for j, i in enumerate(ix):
            masks[i] = (za[i] >= thr[j]).astype(jnp.float32)
    return masks


def global_topk_masks(z_leaves: list[jax.Array], rate) -> list[jax.Array]:
    """Single global top-k across a whole pytree (ablation mode).

    Concatenates all leaves, selects one global threshold, and splits the
    mask back. Exact selector only (used on small models).
    """
    flats = [jnp.abs(x.reshape(-1)).astype(jnp.float32) for x in z_leaves]
    cat = jnp.concatenate(flats)
    thr = exact_threshold(cat, _keep(cat.shape[0], rate))
    return [
        (f >= thr).astype(jnp.float32).reshape(x.shape)
        for f, x in zip(flats, z_leaves, strict=True)
    ]
