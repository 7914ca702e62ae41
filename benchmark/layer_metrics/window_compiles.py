"""Programs JAX compiled or fetched inside the window: 0 expected."""


def read(ctx):
    return ctx["window_compiles"]
