"""Host time per round of ``FLSimulator.run``'s ``fl.dispatch`` span: the
call of the jitted round program until it returns (argument handling and
the enqueue; the device runs on after it). Nothing where the program
opens no such span."""


def read(ctx):
    spans = ctx.view.span_ns("fl.dispatch")
    return sum(spans) * 1e-6 / len(spans) if spans else None
