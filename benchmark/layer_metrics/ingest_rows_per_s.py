"""Rows over the seconds of the set-up's ``append_data`` calls."""


def read(ctx):
    return ctx["setup"]["rows"] / ctx["setup"]["ingest_s"]
