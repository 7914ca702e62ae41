"""Where JAX's persistent compilation cache lives.

``JAX_COMPILATION_CACHE_DIR``, when the caller set it, is the cache and
nothing in this repo sets another. Unset, the cache is
``<checkout>/.jax_cache`` (git-ignored): a fixed path, because the path
is part of the cache key and a directory that moves never hits.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def jax_cache_dir(env: dict | None = None) -> str:
    env = os.environ if env is None else env
    return env.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        _CHECKOUT, ".jax_cache"
    )


def configure_jax_cache() -> str:
    """Turn this process's persistent cache on at ``jax_cache_dir()``.

    Goes through ``jax.config``: importing ``pixie_tpu`` imports jax,
    and jax reads its environment once, at import. What gets cached is
    jax's own rule (compiles of a second or more, unless
    ``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS`` says otherwise): the
    programs worth keeping take a minute on the chip, and the cluster
    tests' timing must not depend on which sub-second program an
    earlier test happened to leave on disk.
    """
    import jax

    d = jax_cache_dir()
    jax.config.update("jax_compilation_cache_dir", d)
    return d


def cpu_env(n_devices: int | None = None, base: dict | None = None) -> dict:
    """Environment for a child process that runs jax on the CPU, with
    ``n_devices`` virtual devices and this process's cache directory."""
    env = dict(os.environ if base is None else base)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = jax_cache_dir(env)
    if n_devices is not None:
        flags = [
            f
            for f in env.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in f
        ]
        flags.append(f"--xla_force_host_platform_device_count={n_devices}")
        env["XLA_FLAGS"] = " ".join(flags)
    return env
