"""Compression state pytrees (error feedback + momenta).

All states are NamedTuples of pytrees so they vmap over clients (leading
axis) in the FL simulator and shard over the ``pod``/``data`` axis in the
distributed runtime without any special handling.

Fields (paper Algorithm 1):
  u — momentum-correction accumulator   U_{k,t}
  v — error-feedback (memory) residual  V_{k,t}
  m — client-side global momentum       M_{k,t}  (built from broadcasts)

Schemes that don't use a field keep it as an empty dict (zero-cost pytree
leaf-less subtree) rather than None so the structure stays stable across
schemes — this lets the FL simulator and the distributed grad-sync treat all
schemes uniformly inside ``lax.scan``/``shard_map``.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.utils import tree_map, tree_zeros_like


class ClientState(NamedTuple):
    u: Any
    v: Any
    m: Any


class ServerState(NamedTuple):
    momentum: Any        # server-side global momentum (DGCwGM only)
    residual: Any        # downlink error-feedback accumulator (topk downlink)


def init_client_state(params, *, use_u: bool, use_v: bool, use_m: bool) -> ClientState:
    zeros = lambda flag: tree_zeros_like(params) if flag else {}
    return ClientState(u=zeros(use_u), v=zeros(use_v), m=zeros(use_m))


def init_server_state(params, *, use_momentum: bool,
                      use_residual: bool = False) -> ServerState:
    zeros = lambda flag: tree_zeros_like(params) if flag else {}
    return ServerState(momentum=zeros(use_momentum), residual=zeros(use_residual))


# ---------------------------------------------------------------------------
# Client-axis layout helpers shared by the round engines (fl/engine.py).
# All three treat the leading axis of every leaf as the client axis, so the
# same code serves the vmap path (device-local stack) and the shard_map path
# (stack laid out over the ``clients`` mesh axis). Gather and scatter run
# under one ``named_scope``, so every engine's copies of the U/V/M stack are
# named in XLA profiles.
# ---------------------------------------------------------------------------

CLIENT_STATE_SCOPE = "round.client_state"


def stack_client_states(state: ClientState, num_clients: int) -> ClientState:
    """Broadcast one client's state to a [K, ...] stack over all clients."""
    return tree_map(
        lambda x: jnp.broadcast_to(x, (num_clients,) + x.shape), state
    )


def gather_client_states(cstates: ClientState, client_idx) -> ClientState:
    """Select the sampled clients' rows ([K, ...] -> [k, ...])."""
    with jax.named_scope(CLIENT_STATE_SCOPE):
        return tree_map(lambda x: jnp.take(x, client_idx, axis=0), cstates)


def scatter_client_states(cstates: ClientState, client_idx, updated: ClientState) -> ClientState:
    """Write the sampled clients' updated rows back into the full stack."""
    with jax.named_scope(CLIENT_STATE_SCOPE):
        return tree_map(
            lambda full, upd: full.at[client_idx].set(upd), cstates, updated
        )


# ---------------------------------------------------------------------------
# Topology layout helpers (fl/engine.py TopologyEngine).
#
# Hierarchical aggregation groups the cohort into ``num_groups`` contiguous
# blocks of the sorted sampled ids; ring aggregation splits it into segments
# of ``hops + 1`` consecutive positions. Both are pure reshapes of the
# client axis, so group sums and per-position gathers stay bitwise-stable
# reorderings of the star engine's single [K, ...] stack.
# ---------------------------------------------------------------------------


def group_sum(stack, num_groups: int):
    """Sum a [K, ...] client-axis stack within ``num_groups`` contiguous
    groups -> [G, ...]. No division: the cloud divides by the cohort size
    exactly once, so ``num_groups=1`` reduces in the same order as the star
    engine's single sum."""
    return tree_map(
        lambda x: jnp.sum(
            x.reshape((num_groups, x.shape[0] // num_groups) + x.shape[1:]),
            axis=1,
        ),
        stack,
    )


def interleave_position_stacks(stacks):
    """Merge per-ring-position [S, ...] stacks back into cohort order.

    ``stacks[p]`` holds segment-major rows for position ``p`` (cohort index
    ``j * len(stacks) + p`` for segment ``j``); stacking on a new axis 1 and
    collapsing restores the original [K, ...] layout."""
    k1 = len(stacks)
    if k1 == 1:
        return stacks[0]
    return tree_map(
        lambda *xs: jnp.stack(xs, axis=1).reshape(
            (k1 * xs[0].shape[0],) + xs[0].shape[1:]
        ),
        *stacks,
    )
