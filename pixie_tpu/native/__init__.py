"""Native (C++) runtime components, built on demand with g++.

The reference keeps its table store, agent shells, and data plane in C++
(SURVEY.md L0-L2); here the host-side hot/cold table slab store is native,
loaded via ctypes. Build is lazy and cached next to the source; when no
toolchain is available, callers fall back to pure-numpy backends.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_LOCK = threading.Lock()
_LIBS: dict[str, object] = {}


def _build(name: str) -> str:
    src = os.path.join(_DIR, f"{name}.cc")
    out = os.path.join(_DIR, f"lib{name}.so")
    if os.path.exists(out) and os.path.getmtime(out) >= os.path.getmtime(src):
        return out
    cmd = [
        "g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
        "-o", out, src,
    ]
    subprocess.run(cmd, check=True, capture_output=True)
    return out


#: The shared objects ``load`` serves (table store ring, CPU segmented
#: fold, host hash join).
LIBRARIES = ("table_ring", "seg_fold", "hash_join")


def rebuild_all() -> bool:
    """Rebuild every library from its ``.cc``, discarding what is on disk.

    For entry points that must not run on a stale or half-built object
    (``chip_smoke.py``): call before the first ``load``. Returns False
    when there is no ``g++`` (callers then run on the numpy backends);
    a compile error raises.
    """
    import shutil

    if shutil.which("g++") is None:
        return False
    with _LOCK:
        _LIBS.clear()
        for name in LIBRARIES:
            out = os.path.join(_DIR, f"lib{name}.so")
            if os.path.exists(out):
                os.remove(out)
            try:
                _build(name)
            except subprocess.CalledProcessError as e:
                raise RuntimeError(
                    f"native build of {name} failed:\n"
                    f"{e.stderr.decode(errors='replace')}"
                ) from None
    return True


def build_executable(name: str) -> str | None:
    """Build native/<name>.cc as a standalone binary (the client CLI
    path, vs ``load``'s shared-object path). Returns the binary path or
    None when the toolchain is unavailable."""
    src = os.path.join(_DIR, f"{name}.cc")
    out = os.path.join(_DIR, name)
    if os.path.exists(out) and os.path.getmtime(out) >= os.path.getmtime(src):
        return out
    try:
        subprocess.run(
            ["g++", "-O3", "-std=c++17", "-pthread", "-o", out, src],
            check=True, capture_output=True,
        )
    except FileNotFoundError:
        return None  # no toolchain: callers skip/degrade
    except subprocess.CalledProcessError as e:
        # A COMPILE error must fail loudly — swallowing it would turn
        # every native-client test into a silent skip.
        raise RuntimeError(
            f"native client build failed:\n{e.stderr.decode(errors='replace')}"
        ) from None
    return out


def load(name: str):
    """Load (building if needed) libpixie native component ``name``.

    Returns the ctypes CDLL, or None when the toolchain/build fails —
    callers must degrade to their Python fallback.
    """
    with _LOCK:
        if name in _LIBS:
            return _LIBS[name]
        try:
            lib = ctypes.CDLL(_build(name))
        except (OSError, subprocess.CalledProcessError, FileNotFoundError):
            lib = None
        _LIBS[name] = lib
        return lib


# -- seg_fold: multi-core segmented fold (engine CPU-backend scatters) -------

#: numpy dtype -> seg_fold value-type code (0 none, 1 i64, 2 f64, 3 f32,
#: 4 u8/bool, 5 i32).
_VAL_TY = {"int64": 1, "float64": 2, "float32": 3, "bool": 4, "uint8": 4,
           "int32": 5}
#: numpy dtype -> output-table type code (tables are i64/f64/f32 only).
_OUT_TY = {"int64": 1, "float64": 2, "float32": 3}

#: (op, out_ty, val_ty) combos implemented by the kernel (fold_one).
_SUPPORTED = frozenset(
    [(0, 1, 0), (0, 2, 0)]  # count
    + [(1, 1, 1), (1, 1, 4), (1, 1, 5), (1, 2, 2), (1, 2, 3), (1, 2, 1),
       (1, 3, 3)]  # sum
    + [(op, ot, vt) for op in (2, 3)
       for ot, vt in ((1, 1), (2, 2), (2, 3), (3, 3))]  # min/max
)


def np_view(a) -> np.ndarray:
    """Zero-copy numpy view of a CPU jax array.

    Both ``np.asarray`` and jax's dlpack export COPY the buffer
    (~9ms per 16MB plane on this class of host); the raw buffer pointer
    shares it. SAFETY: the view aliases the jax buffer — callers must
    keep the source array referenced for the view's (short) lifetime and
    only READ through it, which the fold kernel guarantees.
    """
    if isinstance(a, np.ndarray):
        return a
    try:
        # jax dispatch is async: fence before aliasing the buffer, or the
        # kernel races XLA still writing it (garbage slot ids -> OOB).
        a.block_until_ready()
        ptr = a.unsafe_buffer_pointer()
        dt = np.dtype(str(a.dtype))
        buf = (ctypes.c_char * (a.size * dt.itemsize)).from_address(ptr)
        return np.frombuffer(buf, dtype=dt).reshape(a.shape)
    except Exception:
        return np.ascontiguousarray(np.asarray(a))


def seg_fold_threads() -> int:
    import os as _os

    from ..config import get_flag

    t = get_flag("cpu_fold_threads")
    return t if t > 0 else min(_os.cpu_count() or 1, 16)


def seg_fold_call(gids, g: int, specs, vals, outs) -> bool:
    """Accumulate one window into the output tables.

    ``specs`` is [(op, out_dtype, arg_index|None)] per output; ``vals``
    the per-output contiguous value arrays (None for count); ``outs``
    the (g+1)-row tables accumulated in place. Returns False when the
    kernel is unavailable or a dtype combo is unsupported (caller falls
    back to the XLA fold).
    """
    lib = load("seg_fold")
    if lib is None:
        return False
    n_out = len(specs)
    ops = (ctypes.c_uint8 * n_out)()
    vts = (ctypes.c_uint8 * n_out)()
    ots = (ctypes.c_uint8 * n_out)()
    vptrs = (ctypes.c_void_p * n_out)()
    optrs = (ctypes.c_void_p * n_out)()
    for k, ((op, dt, _a), v, o) in enumerate(zip(specs, vals, outs)):
        ot = _OUT_TY.get(str(np.dtype(dt)))
        vt = 0 if v is None else _VAL_TY.get(str(v.dtype))
        if ot is None or vt is None or (op, ot, vt) not in _SUPPORTED:
            return False
        ops[k], vts[k], ots[k] = op, vt, ot
        vptrs[k] = 0 if v is None else v.ctypes.data
        optrs[k] = o.ctypes.data
    lib.seg_fold(
        gids.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        ctypes.c_longlong(len(gids)), ctypes.c_longlong(g),
        ctypes.c_int(n_out), ops, vts, ots, vptrs, optrs,
        ctypes.c_int(seg_fold_threads()),
    )
    return True


def tdigest_hist_call(gids, vals, g: int, shift: int, w, mw) -> bool:
    """Accumulate the dual t-digest histogram for one window in place.

    ``gids`` i32[n] (>= g rows skipped), ``vals`` f32[n] (non-finite
    skipped, matching batch_to_digest's isfinite mask), ``w``/``mw``
    f32[g * bins] tables; ``bin = monotone_u32(v) >> shift``."""
    lib = load("seg_fold")
    if lib is None:
        return False
    if str(gids.dtype) != "int32" or str(vals.dtype) != "float32":
        return False
    lib.tdigest_hist(
        gids.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        vals.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        ctypes.c_longlong(len(gids)), ctypes.c_longlong(g),
        ctypes.c_int(shift),
        w.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        mw.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        ctypes.c_int(seg_fold_threads()),
    )
    return True


def hash_join_call(build_keys, probe_keys, left_outer: bool):
    """(l_idx, r_idx) i32 arrays for an N:M equijoin over packed i64
    keys, or None when the native library is unavailable. r_idx is -1
    for unmatched probes kept by ``left_outer``."""
    lib = load("hash_join")
    if lib is None:
        return None
    bk = np.ascontiguousarray(build_keys, dtype=np.int64)
    pk = np.ascontiguousarray(probe_keys, dtype=np.int64)
    if len(bk) > (1 << 31) - 2 or len(pk) > (1 << 31) - 2:
        return None  # i32 row-index outputs
    args = [
        bk.ctypes.data_as(ctypes.POINTER(ctypes.c_longlong)),
        ctypes.c_longlong(len(bk)),
        pk.ctypes.data_as(ctypes.POINTER(ctypes.c_longlong)),
        ctypes.c_longlong(len(pk)),
        ctypes.c_int(1 if left_outer else 0),
    ]
    lib.hash_join.restype = ctypes.c_longlong
    # Speculative capacity: 1:1/N:1 joins (the common case) fit in
    # len(pk) pairs, finishing in ONE build+probe; only a fan-out
    # blowup pays the second call at the exact size.
    cap = max(len(pk), 1)
    l_idx = np.empty(cap, dtype=np.int32)
    r_idx = np.empty(cap, dtype=np.int32)
    total = lib.hash_join(
        *args,
        l_idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        r_idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        ctypes.c_longlong(cap),
    )
    if total > cap:
        l_idx = np.empty(total, dtype=np.int32)
        r_idx = np.empty(total, dtype=np.int32)
        lib.hash_join(
            *args,
            l_idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            r_idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            ctypes.c_longlong(total),
        )
    return l_idx[:total], r_idx[:total]


def seg_fold_raw_call(key_planes, key_specs, lo: int, hi: int, g: int,
                      specs, vals, outs):
    """Raw-plane fold: slot ids computed in-kernel from the staged key
    planes. ``key_specs`` is [(kind, dom, off, stride)] per key (kind 0
    i32 dict codes, 1 bool, 2 strided i64). Returns the out-of-domain
    row count, or None when unsupported (caller falls back)."""
    lib = load("seg_fold")
    if lib is None:
        return None
    nk = len(key_specs)
    kptrs = (ctypes.c_void_p * nk)()
    kinds = (ctypes.c_uint8 * nk)()
    doms = (ctypes.c_longlong * nk)()
    offs = (ctypes.c_longlong * nk)()
    strides = (ctypes.c_longlong * nk)()
    for k, (plane, (kind, dom, off, stride)) in enumerate(
        zip(key_planes, key_specs)
    ):
        want = {0: "int32", 1: "bool", 2: "int64"}[kind]
        if str(plane.dtype) != want and not (kind == 1 and str(plane.dtype) == "uint8"):
            return None
        kptrs[k] = plane.ctypes.data
        kinds[k], doms[k], offs[k], strides[k] = kind, dom, off, stride
    n_out = len(specs)
    ops = (ctypes.c_uint8 * n_out)()
    vts = (ctypes.c_uint8 * n_out)()
    ots = (ctypes.c_uint8 * n_out)()
    vptrs = (ctypes.c_void_p * n_out)()
    optrs = (ctypes.c_void_p * n_out)()
    for k, ((op, dt, _a), v, o) in enumerate(zip(specs, vals, outs)):
        ot = _OUT_TY.get(str(np.dtype(dt)))
        vt = 0 if v is None else _VAL_TY.get(str(v.dtype))
        if ot is None or vt is None or (op, ot, vt) not in _SUPPORTED:
            return None
        ops[k], vts[k], ots[k] = op, vt, ot
        vptrs[k] = 0 if v is None else v.ctypes.data
        optrs[k] = o.ctypes.data
    oob = ctypes.c_longlong(0)
    lib.seg_fold_raw(
        kptrs, kinds, doms, offs, strides, ctypes.c_int(nk),
        ctypes.c_longlong(lo), ctypes.c_longlong(hi), ctypes.c_longlong(g),
        ctypes.c_int(n_out), ops, vts, ots, vptrs, optrs,
        ctypes.byref(oob), ctypes.c_int(seg_fold_threads()),
    )
    return int(oob.value)
