"""Device time per round of the ops under the ``round.client_grads``
scope: every sampled client's forward and backward pass."""


def read(ctx):
    ns = ctx.view.scope_ns("round.client_grads")
    return ns * 1e-6 / ctx.rounds if ns else None
