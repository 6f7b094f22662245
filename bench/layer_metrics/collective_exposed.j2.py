"""Share of the traced window in which a collective (the ring's
collective-permutes) ran on a chip and no other operation did, averaged
over the chips (``tracereduce``'s ``collective_exposed_s``)."""


def read(ctx):
    red = ctx["trace"]
    if not red.window_s:
        return None
    return 100.0 * red.collective_exposed_s / red.window_s
