"""Where an engine's arrays live.

An ``Engine`` built with ``device=`` is one node's PEM on a chip of its
own: its tables' windows, its programs, their operands, its folds'
carries and what it fetches all belong to that device, beside other
engines of the same process on theirs. An engine given no device puts
things wherever JAX puts them (the first device), as it always did.

One rule, reached two ways:

- Code with the engine in hand calls ``Engine._put(value)``, which is
  ``put(value, engine.device)``: the value committed to the device.
- Code below the engine has none in hand: a fragment (shared between
  engines through the process's fragment cache) and its operand tables,
  a program called with host arrays alone or with no argument at all (a
  fold's empty state, ``fragment.init_program``), ``jnp`` constructors
  inside a program's trace or made eagerly. It follows
  ``scope(device)``, which ``Engine._on_device()`` enters around every
  request: JAX's own default device for the thread, so uncommitted
  values land there, ``current()`` names it, and ``put(value)`` commits
  to it.

A thread the engine starts inside a request (the window pipeline's
prefetch) does not inherit the scope: what it stages is placed by the
table's ``stage_sharding`` and by ``Engine._stage`` / ``_put``.
"""

from __future__ import annotations

import contextlib

_NO_SCOPE = contextlib.nullcontext()


def scope(device):
    """The thread's work goes to ``device`` until the block ends; no-op
    for None (wherever JAX puts it)."""
    if device is None:
        return _NO_SCOPE
    import jax

    return jax.default_device(device)


def current():
    """The device of the scope this thread is in, or None outside one."""
    import jax

    return jax.config.jax_default_device


def put(value, device=None):
    """``value`` (an array or a tree of them) on ``device``, committed;
    with None, on the scope's device, and outside any scope wherever
    ``jax.device_put(value)`` puts it."""
    import jax

    return jax.device_put(value, device if device is not None else current())
