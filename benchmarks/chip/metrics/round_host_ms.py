"""Host time per round of ``FLSimulator.run``'s serial phases, the spans
``fl.inputs`` (cohort, ids, round index, lr on the device), ``fl.batches``
(the batch provider), ``fl.dispatch``, ``fl.account`` (ledger, history)
and ``fl.on_round`` (the callback): the host's work between one round's
counts and the next round's launch, in which the chip waits. ``fl.wait``
is left out: there the host waits on the chip. Nothing where the program
opens no such spans."""

PHASES = ("fl.inputs", "fl.batches", "fl.dispatch", "fl.account", "fl.on_round")


def read(ctx):
    rounds = len(ctx.view.span_ns("fl.dispatch"))
    if not rounds:
        return None
    return sum(sum(ctx.view.span_ns(name)) for name in PHASES) * 1e-6 / rounds
