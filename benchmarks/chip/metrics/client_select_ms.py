"""Device time per round of the ops under the ``compress.select`` scope:
the top-k selection of every sampled client (``TopKSelector.select`` in
``core/stages.py``), nested inside ``round.client_compress``. Nothing where
the round program has no such scope."""

SCOPE = "compress.select"


def read(ctx):
    if not any(SCOPE in path for path in ctx.view.scopes.values()):
        return None
    return ctx.view.scope_ns(SCOPE) * 1e-6 / ctx.rounds
