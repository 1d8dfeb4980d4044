"""Host time per round of the benchmark's batch provider, the
``bench.batch_build`` span around the call that ``FLSimulator.run`` makes
for each round's client batches."""


def read(ctx):
    spans = ctx.view.span_ns("bench.batch_build")
    return sum(spans) * 1e-6 / len(spans) if spans else None
