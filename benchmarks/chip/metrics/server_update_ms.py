"""Device time per round of the ops under the ``round.server_aggregate``
and ``round.apply_update`` scopes: the mean of the payloads, the broadcast
and the parameter update."""


def read(ctx):
    ns = ctx.view.scope_ns("round.server_aggregate", "round.apply_update")
    return ns * 1e-6 / ctx.rounds if ns else None
