"""Share of the traced window in which no op ran on the device (%)."""


def reduce(ctx):
    view = ctx["view"]
    return 100.0 * (1.0 - view.busy_s / view.window_s)
