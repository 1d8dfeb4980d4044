"""Device time per round of the round program's ops under none of its
``round.*`` scopes: what no layer owns yet (copies XLA inserts without
metadata, for one). Nothing where the round program lacks the
``round.client_state`` scope, without which the client-state copies
would count here."""


def _owned(path: str) -> bool:
    return any(part.startswith("round.") for part in path.split("/"))


def read(ctx):
    view = ctx.view
    if not any("round.client_state" in path for path in view.scopes.values()):
        return None
    ns = sum(op.end - op.start for op in view.ops
             if op.module == view.program and not _owned(view.scope_of(op)))
    return ns * 1e-6 / ctx.rounds
