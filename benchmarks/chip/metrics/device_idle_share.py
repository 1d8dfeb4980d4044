"""Share of the traced window in which no op ran on the device, in
percent: 100 (1 - union of the op intervals / window)."""


def read(ctx):
    window = ctx.view.window_ns
    return 100.0 * (1.0 - ctx.view.busy_ns() / window) if window > 0 else None
