"""Bytes a snapshot holds: every array and string reachable from it.

The walk follows instance attributes (``__dict__`` and ``__slots__``),
dicts, lists, tuples and sets from the snapshot object, and counts each
numpy array's buffer once (views share their base), each string by
``sys.getsizeof`` and each container by its own ``sys.getsizeof``.  So
it counts G's triples, the indexes, the molecule tables and the term
dictionary as they sit in memory, whatever their names, and it does not
ask the program for its own accounting.  Functions, classes, modules
and device arrays are not host data and are skipped.
"""
from __future__ import annotations

import sys
import types

import numpy as np

_SKIP = (types.FunctionType, types.BuiltinFunctionType, types.MethodType,
         types.ModuleType, type, int, float, bool, complex, type(None),
         bytes)


def snapshot_bytes(root) -> int:
    seen: set[int] = set()
    buffers: set[int] = set()
    total = 0
    stack = [root]
    while stack:
        obj = stack.pop()
        if id(obj) in seen or isinstance(obj, _SKIP) or \
                type(obj).__module__.startswith(("jax", "jaxlib")):
            continue
        seen.add(id(obj))
        if isinstance(obj, np.ndarray):
            base = obj
            while isinstance(base.base, np.ndarray):
                base = base.base
            if id(base) not in buffers:
                buffers.add(id(base))
                total += base.nbytes
            continue
        if isinstance(obj, str):
            total += sys.getsizeof(obj)
            continue
        if isinstance(obj, dict):
            total += sys.getsizeof(obj)
            stack.extend(obj.keys())
            stack.extend(obj.values())
            continue
        if isinstance(obj, (list, tuple, set, frozenset)):
            total += sys.getsizeof(obj)
            stack.extend(obj)
            continue
        if hasattr(obj, "__dict__"):
            stack.append(vars(obj))
        for cls in type(obj).__mro__:
            for slot in getattr(cls, "__slots__", ()):
                if hasattr(obj, slot):
                    stack.append(getattr(obj, slot))
    return total
