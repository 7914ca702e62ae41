"""Process start to the first timed refresh: import, data from the
seed, ingest to residency, warm-up. The reference is not in it."""


def read(ctx):
    return ctx["setup"]["setup_s"]
