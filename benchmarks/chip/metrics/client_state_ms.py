"""Device time per round of the ops under the ``round.client_state`` scope:
the gather of the sampled clients' rows of the U/V/M stack and their
scatter back (``core/state.py``). Nothing where the round program has no
such scope."""

SCOPE = "round.client_state"


def read(ctx):
    if not any(SCOPE in path for path in ctx.view.scopes.values()):
        return None
    return ctx.view.scope_ns(SCOPE) * 1e-6 / ctx.rounds
