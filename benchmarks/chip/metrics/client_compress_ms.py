"""Device time per round of the ops under the ``round.client_compress``
scope: momentum correction, global-momentum fusion, top-k selection and the
error-feedback update of every sampled client."""


def read(ctx):
    ns = ctx.view.scope_ns("round.client_compress")
    return ns * 1e-6 / ctx.rounds if ns else None
