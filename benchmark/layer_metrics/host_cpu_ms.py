"""Process CPU time per refresh: ``time.process_time`` over the window,
all threads, over the refreshes completed. Per-layer only: on a host
whose cores are shared it spread 7-8% run to run (ledger, PR 23)."""


def read(ctx):
    n = len(ctx["window"]["refreshes"])
    return ctx["window"]["cpu_s"] * 1e3 / n if n else None
