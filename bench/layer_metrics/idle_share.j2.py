"""Share of the traced window in which no operation ran on the device,
averaged over the cell's chips."""


def read(ctx):
    red = ctx["trace"]
    return 100.0 * (1.0 - red.busy_s / red.window_s)
