"""Seconds of set-up spent in the warm-up refreshes."""


def read(ctx):
    return ctx["setup"]["warmup_s"]
