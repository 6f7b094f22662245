"""Megabytes one agent ships per step, as the program's ``CommsLedger``
records them at trace time: both consensus streams (x and u), every leaf
once per ppermute round."""


def read(ctx):
    b = ctx["counters"].get("wire_bytes_per_step")
    return None if b is None else b / 1e6
