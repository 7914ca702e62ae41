"""Dictionary encoding for STRING columns.

TPU-first design: strings never reach the device. At staging time each
string column is encoded into int32 dictionary ids; all device-side ops
(equality filters, group-by keys, join keys) are id ops. Host-side UDFs
(regex, json, normalization) transform the *dictionary*, not the rows —
a dictionary with K distinct values is transformed in O(K) instead of
O(rows).

Reference contrast: Carnot ships raw strings through Arrow StringArrays
and hashes them per-row in agg/join maps (``src/carnot/exec/row_tuple.h``).
"""

from __future__ import annotations

import hashlib
import struct
import threading
from collections import OrderedDict
from typing import Iterable

import numpy as np

NULL_ID = -1


class DictImage:
    """What a string -> string function makes of a dictionary's first
    ``n`` strings: ``dict`` holds its results, ``remap[old_id]`` is a
    result's id there. ``derived`` is the holder's to hang on it what
    it makes of the remap once (the binder's padded device table)."""

    __slots__ = ("dict", "remap", "n", "derived", "source")

    def __init__(self, dict_: "StringDictionary", remap: np.ndarray,
                 source: tuple = ()):
        self.dict = dict_
        self.remap = remap
        self.n = len(remap)
        self.derived: dict = {}
        self.source = source  # the ``content_key`` it is the image of


# Images by (function key, source ``content_key``): equal content from a
# fresh object (a dictionary decoded from the wire) finds the image its
# twin made. LRU: an image of a large dictionary holds 4 B a string.
_IMAGES: "OrderedDict[tuple, DictImage]" = OrderedDict()
_IMAGES_MAX = 32
_IMAGES_LOCK = threading.Lock()


class StringDictionary:
    """Append-only string <-> int32 id mapping."""

    __slots__ = ("_str_to_id", "_strings", "_fp", "_fp_len", "_fp_digest",
                 "_fp_lock", "_images", "_images_lock", "_byte_lengths")

    def __init__(self, strings: Iterable[str] = ()):
        self._strings: list[str] = []
        self._str_to_id: dict[str, int] = {}
        # Incremental content fingerprint (content_key): hasher state,
        # how many strings it has absorbed, and the digest at that
        # length. Lazy — dictionaries that never cross a cache key pay
        # nothing. Per-dictionary lock: a first-call fingerprint of a
        # LARGE ingest dictionary hashes its whole string table, and a
        # process-wide lock would stall every other thread's compile
        # fast path behind that one dictionary.
        self._fp = None
        self._fp_len = 0
        self._fp_digest = b""
        self._fp_lock = threading.Lock()
        # Images of this dictionary under string -> string functions
        # (``image``), by the function's key.
        self._images: dict = {}
        self._images_lock = threading.Lock()
        # UTF-8 byte length of each string (``byte_lengths``), extended
        # as the dictionary grows.
        self._byte_lengths = np.zeros(0, dtype=np.int32)
        for s in strings:
            self.get_or_add(s)

    def byte_lengths(self) -> np.ndarray:
        """int32[len]: the UTF-8 bytes of each string, by id. Amortized
        O(new strings): the dictionary is append-only, so the lengths in
        hand stay good and only the strings past them are measured."""
        have = self._byte_lengths
        n = len(self._strings)
        if len(have) < n:
            have = np.concatenate([have, np.fromiter(
                (len(s.encode("utf-8", "surrogatepass"))
                 for s in self._strings[len(have):n]),
                np.int32, n - len(have))])
            self._byte_lengths = have
        return have[:n]

    def content_key(self) -> tuple:
        """Content-addressed identity: ``(len, digest)`` over the
        ordered string table.

        The fragment cache (``exec/fragment.compile_fragment_cached``)
        keys dictionaries by THIS instead of ``id()``: bridge payloads
        that cross the wire decode into fresh ``StringDictionary``
        objects every query, so identity-keyed caching recompiled the
        merge tier's XLA programs on every distributed query — equal
        content must hit. Sound because the dictionary is append-only:
        two dictionaries with equal (ordered) content resolve every id
        and every compile-time ``lookup`` identically, and a dictionary
        that later GROWS simply produces a new key (its first
        ``len`` entries — all any cached fragment resolved against —
        are immutable). Amortized O(new strings): the hash state
        extends incrementally under the dictionary's own lock (a query
        thread can fingerprint while ingest appends on another).
        """
        with self._fp_lock:
            n = len(self._strings)
            if self._fp is None:
                self._fp = hashlib.blake2b(digest_size=16)
            if n > self._fp_len:
                h = self._fp
                for s in self._strings[self._fp_len:n]:
                    b = s.encode("utf-8", "surrogatepass")
                    # Length-prefixed: ("ab","c") never collides with
                    # ("a","bc").
                    h.update(struct.pack("<I", len(b)))
                    h.update(b)
                self._fp_len = n
                self._fp_digest = h.digest()
            elif not self._fp_digest and n == 0:
                self._fp_digest = self._fp.digest()
            return (n, self._fp_digest)

    def __len__(self) -> int:
        return len(self._strings)

    def get_or_add(self, s: str) -> int:
        sid = self._str_to_id.get(s)
        if sid is None:
            sid = len(self._strings)
            self._str_to_id[s] = sid
            self._strings.append(s)
        return sid

    def lookup(self, s: str) -> int:
        """Id for ``s`` or NULL_ID if unseen (for filter literals)."""
        return self._str_to_id.get(s, NULL_ID)

    def encode(self, values: Iterable[str]) -> np.ndarray:
        vals = list(values)
        return np.fromiter((self.get_or_add(v) for v in vals), dtype=np.int32, count=len(vals))

    def decode(self, ids: np.ndarray) -> np.ndarray:
        ids = np.asarray(ids)
        table = np.empty(len(self._strings) + 1, dtype=object)
        table[:-1] = self._strings
        table[-1] = None  # slot for out-of-range / NULL_ID
        safe = np.where((ids >= 0) & (ids < len(self._strings)), ids, len(self._strings))
        return table[safe]

    def decode_one(self, sid: int) -> str | None:
        return self._strings[sid] if 0 <= sid < len(self._strings) else None

    @property
    def strings(self) -> list[str]:
        return self._strings

    def transform(self, fn) -> tuple["StringDictionary", np.ndarray]:
        """Host UDF escape hatch: apply ``fn`` to every distinct string.

        Returns (new_dict, remap) where ``remap[old_id] -> new_id``; device
        side applies the remap as a gather. O(K distinct), not O(rows).
        """
        img = self._extended(fn, None, len(self._strings))
        return img.dict, img.remap

    def _extended(self, fn, have: "DictImage | None", n: int) -> DictImage:
        """The image of the first ``n`` strings under ``fn``: ``have``
        (the image of fewer) taken on by the strings it lacks. The
        results' dictionary is shared with ``have`` and grows in place:
        it is append-only, so the ids ``have`` handed out stay good."""
        new = StringDictionary() if have is None else have.dict
        done = 0 if have is None else have.n
        remap = np.empty(n, dtype=np.int32)
        remap[:done] = () if have is None else have.remap
        strings = self._strings
        for i in range(done, n):
            remap[i] = new.get_or_add(fn(strings[i]))
        return DictImage(new, remap)

    def image(self, fn, key) -> tuple:
        """``transform`` remembered: (image, ``hit`` / ``extend`` /
        ``miss``, the strings ``fn`` was run on).

        ``key`` stands for ``fn`` (the UDF and its literal arguments;
        hashable, and it holds whatever its identity rests on). An image
        is remembered on this dictionary and, by ``content_key``, for
        every dictionary of equal content, and it grows as its source
        does, as ``content_key`` itself: a second bind of the same
        function pays for no string, a dictionary that grew by k for k.
        Binds of one dictionary queue on its lock: the second of two
        that race waits for the first and hits."""
        with self._images_lock:
            ck = self.content_key()
            n = ck[0]
            mine = self._images.get(key)
            if mine is not None and mine.n == n:
                return mine, "hit", 0
            if mine is None:
                with _IMAGES_LOCK:
                    shared = _IMAGES.get((key, ck))
                    if shared is not None:
                        _IMAGES.move_to_end((key, ck))
                if shared is not None:
                    self._images[key] = shared
                    return shared, "hit", 0
            img = self._extended(fn, mine, n)
            img.source = ck
            self._images[key] = img
            with _IMAGES_LOCK:
                if mine is not None:  # the shorter image it grew out of
                    _IMAGES.pop((key, mine.source), None)
                _IMAGES[(key, ck)] = img
                while len(_IMAGES) > _IMAGES_MAX:
                    _IMAGES.popitem(last=False)
            if mine is None:
                return img, "miss", n
            return img, "extend", n - mine.n

    def union(self, other: "StringDictionary") -> tuple["StringDictionary", np.ndarray, np.ndarray]:
        """Merged dict + id remaps for self and other (join/union alignment)."""
        merged = StringDictionary(self._strings)
        remap_self = np.arange(len(self._strings), dtype=np.int32)
        remap_other = np.fromiter(
            (merged.get_or_add(s) for s in other._strings),
            dtype=np.int32,
            count=len(other._strings),
        )
        return merged, remap_self, remap_other
