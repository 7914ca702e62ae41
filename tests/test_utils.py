"""Datastore, bloom filter, UPID tests."""

import numpy as np
import pytest

from pixie_tpu.utils import BloomFilter, MemoryDatastore, SqliteDatastore, UPID
from pixie_tpu.utils.upid import pack_planes, unpack_planes


@pytest.mark.parametrize("backend", ["memory", "sqlite"])
class TestDatastore:
    def _mk(self, backend, tmp_path):
        if backend == "memory":
            return MemoryDatastore()
        return SqliteDatastore(str(tmp_path / "kv.db"))

    def test_crud(self, backend, tmp_path):
        ds = self._mk(backend, tmp_path)
        assert ds.get("a") is None
        ds.set("a", b"1")
        ds.set("a", b"2")  # upsert
        assert ds.get("a") == b"2"
        ds.delete("a")
        assert ds.get("a") is None

    def test_prefix_scan(self, backend, tmp_path):
        ds = self._mk(backend, tmp_path)
        for k in ("agent/1", "agent/2", "tracepoint/1"):
            ds.set(k, k.encode())
        got = ds.get_with_prefix("agent/")
        assert [k for k, _ in got] == ["agent/1", "agent/2"]
        ds.delete_with_prefix("agent/")
        assert ds.get_with_prefix("agent/") == []
        assert ds.get("tracepoint/1") == b"tracepoint/1"


def test_sqlite_persists(tmp_path):
    p = str(tmp_path / "kv.db")
    ds = SqliteDatastore(p)
    ds.set("cron/1", b"script")
    ds.close()
    ds2 = SqliteDatastore(p)
    assert ds2.get("cron/1") == b"script"


class TestBloomFilter:
    def test_membership(self):
        bf = BloomFilter(1000, 0.01)
        items = [f"pod-{i}" for i in range(500)]
        for it in items:
            bf.insert(it)
        assert all(bf.contains(it) for it in items)
        fp = sum(bf.contains(f"other-{i}") for i in range(2000))
        assert fp < 2000 * 0.05  # within a few x of the 1% target

    def test_serialization_round_trip(self):
        bf = BloomFilter(100)
        bf.insert("svc/default/frontend")
        data = bf.to_bytes()
        bf2 = BloomFilter.from_bytes(data)
        assert bf2.contains("svc/default/frontend")
        assert not bf2.contains("svc/default/backend")


class TestUPID:
    def test_pack_unpack(self):
        u = UPID(asid=7, pid=1234, start_ts=1_700_000_000_000_000_000)
        v = u.value()
        assert UPID.from_value(v) == u
        assert UPID.parse(str(u)) == u

    def test_planes_round_trip(self):
        ups = [UPID(1, 2, 3), UPID(0xFFFFFFFF, 0xFFFFFFFF, 2**64 - 1)]
        hi, lo = pack_planes(ups)
        assert hi.dtype == np.uint64
        assert unpack_planes(hi, lo) == ups

    def test_device_column_round_trip(self):
        from pixie_tpu.types.batch import HostBatch
        from pixie_tpu.types.dtypes import DataType
        from pixie_tpu.types.relation import Relation

        ups = [UPID(5, 99, 123456789), UPID(6, 100, 987654321)]
        hi, lo = pack_planes(ups)
        hb = HostBatch.from_pydict(
            {"upid": np.stack([hi, lo], axis=1)},
            relation=Relation([("upid", DataType.UINT128)]),
        )
        back = hb.to_device().to_host().to_pydict()["upid"]
        assert unpack_planes(back[:, 0], back[:, 1]) == ups


class TestELFReader:
    """obj_tools parity: symbolize addresses in our own native library."""

    def test_symbols_and_addr_lookup(self):
        import os

        from pixie_tpu.utils.elf import ELFReader

        so = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "pixie_tpu", "native", "libtable_ring.so",
        )
        r = ELFReader(so)
        assert r.symbols, "no FUNC symbols parsed"
        names = {s.name for s in r.symbols}
        # The slab-store C API must be visible.
        assert any("ring" in n or "table" in n for n in names), sorted(names)[:10]
        # Round-trip: an exported symbol's address resolves back to it.
        s = r.symbols[len(r.symbols) // 2]
        got = r.addr_to_symbol(s.addr + max(s.size // 2, 0))
        assert got == s.name
        assert r.addr_to_symbol(0) is None

    def test_rejects_non_elf(self, tmp_path):
        import pytest as _pytest

        from pixie_tpu.utils.elf import ELFError, ELFReader

        p = tmp_path / "x"
        p.write_bytes(b"not an elf")
        with _pytest.raises(ELFError):
            ELFReader(str(p))


class TestJaxCacheDir:
    """utils/cache.py: the caller's JAX_COMPILATION_CACHE_DIR is the
    cache; unset, it is <checkout>/.jax_cache — never a path built from
    /tmp, a pid, a time or the host."""

    def test_env_wins(self):
        from pixie_tpu.utils.cache import jax_cache_dir

        assert jax_cache_dir({"JAX_COMPILATION_CACHE_DIR": "/some/dir"}) == "/some/dir"

    def test_default_is_in_the_checkout(self):
        import os

        from pixie_tpu.utils.cache import jax_cache_dir

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert jax_cache_dir({}) == os.path.join(repo, ".jax_cache")
        assert jax_cache_dir({"JAX_COMPILATION_CACHE_DIR": ""}) == jax_cache_dir({})

    def test_configure_sets_this_process(self):
        """configure_jax_cache() goes through jax.config (the env var is
        read once, at jax's import) and names no other directory."""
        import jax

        from pixie_tpu.utils.cache import configure_jax_cache, jax_cache_dir

        was = jax.config.jax_compilation_cache_dir
        try:
            assert configure_jax_cache() == jax_cache_dir()
            assert jax.config.jax_compilation_cache_dir == jax_cache_dir()
        finally:
            jax.config.update("jax_compilation_cache_dir", was)

    @pytest.mark.parametrize("base", [{}, {"JAX_COMPILATION_CACHE_DIR": "/x/y"}])
    def test_cpu_env_for_children(self, base):
        from pixie_tpu.utils.cache import cpu_env, jax_cache_dir

        env = cpu_env(n_devices=4, base={**base, "XLA_FLAGS": "--foo "
                      "--xla_force_host_platform_device_count=8"})
        assert env["JAX_PLATFORMS"] == "cpu"
        assert env["JAX_COMPILATION_CACHE_DIR"] == jax_cache_dir(base)
        assert env["XLA_FLAGS"].split() == [
            "--foo", "--xla_force_host_platform_device_count=4"
        ]


def test_every_flag_is_read_somewhere():
    """A flag that no code reads is not an option: every defined flag's
    name is a string literal somewhere in the package outside config.py
    (where ``get_flag`` / ``override_flag`` / a deploy role names it)."""
    import ast
    import pathlib

    import pixie_tpu
    from pixie_tpu import config

    root = pathlib.Path(pixie_tpu.__file__).parent
    literals = set()
    for path in root.rglob("*.py"):
        if path == root / "config.py":
            continue
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.Constant) and isinstance(node.value, str):
                literals.add(node.value)
    assert sorted(set(config.all_flags()) - literals) == []
