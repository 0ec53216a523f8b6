"""A job's records as a read-only mapping over two sorted host arrays.

``JobResult.records`` is a :class:`Records`: the live keys, ascending,
and their reduced values, as the finish program leaves them. Reading it
builds no dict: ``records[k]``, ``get`` and ``in`` search the key array,
and iteration (``keys()``, ``values()``, ``items()``) walks the arrays
in key order through a ``memoryview`` of each array, which hands out
Python numbers without an intermediate list. ``dict(records)`` makes a
mutable copy; ``key_array`` and ``value_array`` hand out the arrays
themselves (read-only) for bulk use, as the built-in finalizers do.
"""
from __future__ import annotations

import operator
from collections.abc import ItemsView, KeysView, Mapping, ValuesView

import numpy as np


def _read_only(a: np.ndarray) -> np.ndarray:
    a = np.ascontiguousarray(a).view()   # the caller's array stays writable
    a.flags.writeable = False
    return a


class Records(Mapping):
    """``{key: value}`` over ``keys`` (integers, no duplicates) and
    ``values``. Keys that do not come ascending are sorted once here; the
    engine's come sorted. A two-lane key reads ``(major << 32) | minor``.
    """

    __slots__ = ("_keys", "_values")

    def __init__(self, keys, values):
        keys, values = np.asarray(keys), np.asarray(values)
        if keys.ndim != 1 or keys.shape != values.shape:
            raise ValueError(f"keys {keys.shape} and values {values.shape} "
                             "must be 1-D and of one length")
        if not np.issubdtype(keys.dtype, np.integer):
            raise TypeError(f"keys must be integers, not {keys.dtype}")
        if not (keys[1:] > keys[:-1]).all():
            order = np.argsort(keys, kind="stable")
            keys, values = keys[order], values[order]
            if (keys[1:] == keys[:-1]).any():
                raise ValueError("duplicate keys")
        self._keys, self._values = _read_only(keys), _read_only(values)

    @property
    def key_array(self) -> np.ndarray:
        """The keys, ascending (read-only)."""
        return self._keys

    @property
    def value_array(self) -> np.ndarray:
        """The values, in key order (read-only)."""
        return self._values

    def __len__(self) -> int:
        return len(self._keys)

    def __getitem__(self, key):
        try:
            k = operator.index(key)
        except TypeError:
            raise KeyError(key) from None
        i = int(np.searchsorted(self._keys, k))
        if i == len(self._keys) or self._keys[i].item() != k:
            raise KeyError(key)
        return self._values[i].item()

    def __iter__(self):
        return iter(memoryview(self._keys))

    def keys(self):
        return _Keys(self)

    def values(self):
        return _Values(self)

    def items(self):
        return _Items(self)

    def __eq__(self, other):
        if isinstance(other, Records):
            return (np.array_equal(self._keys, other._keys)
                    and np.array_equal(self._values, other._values))
        if not isinstance(other, Mapping):
            return NotImplemented
        missing = object()
        return len(self) == len(other) and all(
            other.get(k, missing) == v for k, v in self.items())

    __hash__ = None

    def __repr__(self) -> str:
        head = ", ".join(f"{k}: {v}" for k, v in zip(
            self._keys[:8].tolist(), self._values[:8].tolist()))
        more = ", ..." if len(self) > 8 else ""
        return f"Records({len(self)} records: {{{head}{more}}})"


class _Keys(KeysView):
    __slots__ = ()

    def __iter__(self):
        return iter(self._mapping)


class _Values(ValuesView):
    __slots__ = ()

    def __iter__(self):
        return iter(memoryview(self._mapping._values))


class _Items(ItemsView):
    __slots__ = ()

    def __iter__(self):
        m = self._mapping
        return zip(memoryview(m._keys), memoryview(m._values))


def as_records(records: Mapping) -> Records:
    """``records`` as a :class:`Records`: itself if it is one, else its
    items copied into int64 arrays (a plain dict handed to a
    ``finalize``)."""
    if isinstance(records, Records):
        return records
    n = len(records)
    return Records(np.fromiter(records.keys(), np.int64, n),
                   np.fromiter(records.values(), np.int64, n))
