"""TPU-shaped prefix sums.

XLA:TPU lowers a 1-D 64-bit ``cumsum`` to a variadic reduce-window over
(hi, lo) u32 pairs and stages the ENTIRE operand in scoped vmem — at
multi-million-row windows that is a guaranteed compile failure
("Scoped allocation ... exceeded scoped vmem limit", seen at 64 MiB vs
the 16 MiB cap). The classic two-level blocked scan sidesteps it:
chunk-local cumsums tile over the major axis (each row is one vmem-
resident lane), and only the tiny chunk-totals vector takes the scalar
scan. Integer wraparound keeps every step exact, so the blocked form is
bit-identical to the flat one.

Reference parity: this replaces the per-group accumulation loops of
``src/carnot/exec/agg_node.cc`` (value-wise adds into hash-table slots)
for the sorted-segment reduction strategy documented in
``udf/builtins/math_ops.py``.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

#: Chunk width: one (rows, _CHUNK) i64 row = 64 KiB, comfortably inside
#: a vmem tile; reduce-window then scans the minor axis per-row.
_CHUNK = 8192

#: Flat cumsum below this operand size compiles fine (the scoped-vmem
#: cap is 16 MiB; stay well under) and avoids the reshape/pad
#: round-trip. In elements: 1M for i64/f64, 2M for i32.
_FLAT_MAX_BYTES = 1 << 23
#: Back-compat alias used by tests: the i64 flat-path element bound.
_FLAT_MAX = _FLAT_MAX_BYTES // 8


def _needs_blocking(x, force: bool) -> bool:
    """Size-based, backend-independent: the blocked form is used above
    the threshold on EVERY backend (CPU pays only a cheap reshape, and
    lowering-target-vs-default-backend mismatches can't reintroduce the
    TPU compile failure). ``force=True`` picks the blocked path at any
    size — the tests' hook for exercising it on small inputs."""
    if force:
        return True
    (n,) = x.shape
    return n * np.dtype(x.dtype).itemsize > _FLAT_MAX_BYTES


def _totals_scan(totals, op, identity):
    """Inclusive scan of the [c] chunk totals in log2(c) shifted
    combines (Hillis-Steele), exact for integers. Not ``jnp.cumsum``:
    inside a ``while`` body (the engine's scan-fold program) XLA:TPU
    gives even this tiny 64-bit reduce-window a scoped-vmem stack past
    the 16 MiB limit, and the program fails to compile."""
    (c,) = totals.shape
    k = 1
    while k < c:
        shifted = jnp.concatenate(
            [jnp.full(k, identity, totals.dtype), totals[:-k]]
        )
        totals = op(totals, shifted)
        k *= 2
    return totals


def blocked_cumsum(x: jnp.ndarray, force: bool = False) -> jnp.ndarray:
    """Inclusive 1-D cumsum, exact for integers, safe to compile on TPU
    at any length. Equals ``jnp.cumsum(x)`` elementwise for integer
    dtypes (wraparound included) on every backend; float association
    order depends on which path the size threshold selects, so floats
    should not rely on bit-reproducibility across sizes."""
    (n,) = x.shape
    if not _needs_blocking(x, force):
        return jnp.cumsum(x)
    c = -(-n // _CHUNK)
    pad = c * _CHUNK - n
    x2 = jnp.pad(x, (0, pad)).reshape(c, _CHUNK)
    within = jnp.cumsum(x2, axis=1)
    # Exclusive prefix of the chunk totals: a length-c scan (c = n/8192).
    totals = _totals_scan(within[:, -1], jnp.add, 0)
    prefix = jnp.concatenate([jnp.zeros(1, x.dtype), totals[:-1]])
    return (within + prefix[:, None]).reshape(-1)[:n]


def blocked_cummax(x: jnp.ndarray, force: bool = False) -> jnp.ndarray:
    """Inclusive 1-D cumulative max with the same blocked structure as
    :func:`blocked_cumsum` (``lax.cummax`` has the identical scoped-vmem
    reduce-window lowering on TPU)."""
    import jax

    if not _needs_blocking(x, force):
        return jax.lax.cummax(x)
    (n,) = x.shape
    if x.dtype == jnp.bool_:
        lowest = False  # cumulative OR: False is the identity
    elif jnp.issubdtype(x.dtype, jnp.integer):
        lowest = np.iinfo(np.dtype(x.dtype)).min
    else:
        lowest = -jnp.inf
    c = -(-n // _CHUNK)
    pad = c * _CHUNK - n
    x2 = jnp.pad(x, (0, pad), constant_values=lowest).reshape(c, _CHUNK)
    within = jax.lax.cummax(x2, axis=1)
    totals = _totals_scan(within[:, -1], jnp.maximum, lowest)
    prefix = jnp.concatenate([jnp.full(1, lowest, x.dtype), totals[:-1]])
    return jnp.maximum(within, prefix[:, None]).reshape(-1)[:n]
