"""What it takes to find an engine's compiled programs again, and their
scope tables — built on demand, never at start-up.

At a program's FIRST call (``telemetry/trace.py FirstCall``, which both
engines go through) :meth:`Programs.record` keeps the program's name, the
jitted function and the ABSTRACT signature of the call's real arguments:
a ``jax.ShapeDtypeStruct`` a leaf, with the leaf's sharding where the array
was committed to one — microseconds, no device array, nothing lowered,
compiled or parsed.  An engine holds its own :class:`Programs` and hands it
to :func:`trace.keep` under the role ``programs``, so whoever drove the
engine reads ``trace.kept("programs")`` after ``close()`` — the newest
engine's, as ``kept("serve")`` is the newest ring.  (A record holds the
jitted function, hence whatever its body closes over, until the next engine
takes the slot.)

On first demand :meth:`Programs.table` runs
``fn.lower(*abstract).compile()``: the same avals and shardings as the call
that built the program, so JAX's own caches hand back the executable that is
running — no trace (the engine's recompile sentry does not tick), no backend
compile (``backend_compiles`` in the table says how many there were: 0) —
and reads its scheduled text ONCE (``telemetry/hlo_text.py scope_table``).
:meth:`Programs.lowered` gives the same program's ``Lowered`` for a cost
analysis (``telemetry/flops.py`` prices the program that was BUILT).
"""

from __future__ import annotations

import time
from typing import Any, Dict, Tuple

__all__ = ["Programs", "abstract"]


def abstract(tree: Any) -> Any:
    """``tree`` with every array leaf replaced by its ``ShapeDtypeStruct``:
    shape, dtype, weak type, and the sharding of a jax array that was
    committed to one (an uncommitted array, a numpy operand and a Python
    scalar lower as they did in the call: unspecified)."""
    import jax

    def leaf(x):
        if isinstance(x, jax.Array):
            return jax.ShapeDtypeStruct(
                x.shape, x.dtype, weak_type=x.weak_type,
                sharding=x.sharding if x.committed else None)
        aval = jax.api_util.shaped_abstractify(x)
        return jax.ShapeDtypeStruct(aval.shape, aval.dtype,
                                    weak_type=aval.weak_type)

    return jax.tree_util.tree_map(leaf, tree)


class Programs:
    """The programs one engine has built, by the name its sentry registered
    (``decode``, ``prefill[4x128]``, ``verify``, ``draft``, ``train_step``
    ...)."""

    def __init__(self) -> None:
        #: name -> (jitted function, abstract args, abstract kwargs)
        self.records: Dict[str, Tuple[Any, tuple, dict]] = {}
        self._tables: Dict[str, Dict[str, Any]] = {}

    def record(self, name: str, fn, args: tuple, kwargs: dict) -> None:
        """Keep what finds ``name``'s executable again (module docstring).
        Called with the first call's own arguments, BEFORE the call: a
        donated array is gone after it."""
        self.records[name] = (fn, abstract(args), abstract(kwargs))
        self._tables.pop(name, None)

    def signature(self, name: str) -> Tuple[tuple, dict]:
        """The abstract ``(args, kwargs)`` ``name`` was built for."""
        _, args, kwargs = self.records[name]
        return args, kwargs

    def lowered(self, name: str):
        """``jax.stages.Lowered`` of the program as it was built."""
        fn, args, kwargs = self.records[name]
        return fn.lower(*args, **kwargs)

    def table(self, name: str) -> Dict[str, Any]:
        """The scope table of ``name`` (``hlo_text.scope_table``) with
        ``program``, ``build_s`` (seconds this took) and
        ``backend_compiles`` (compiles it cost: 0 — None where nobody
        counts them); built once, then kept."""
        got = self._tables.get(name)
        if got is None:
            from ..analysis import sentry
            from . import hlo_text

            t0, before = time.perf_counter(), sentry.backend_compiles()
            text = self.lowered(name).compile().as_text()
            after = sentry.backend_compiles()
            got = hlo_text.scope_table(text)
            got.update(
                program=name, build_s=time.perf_counter() - t0,
                backend_compiles=None if before is None else after - before)
            self._tables[name] = got
        return got

    def tables(self) -> Dict[str, Dict[str, Any]]:
        """``{name: table}`` of every program recorded so far."""
        return {name: self.table(name) for name in self.records}

