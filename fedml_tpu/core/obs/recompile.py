"""Recompile forensics: when a dispatch compiles past its program's first
compile, name the argument leaves whose abstract shape moved.

Every round program and serving program is pinned to compile once. The
dispatch seams (``TPUSimulator._traced``, which the async engine's pour
programs go through too, and ``DecodeScheduler._dispatch``) hand the
tracker the call's arguments and the number of backend compiles the call
triggered. A call that compiled nothing costs one comparison: the
signature (tree path and ``dtype[shape]`` of every leaf, never a value)
is taken only on a call that compiled, after the call (a donated
``jax.Array`` keeps its ``shape`` and ``dtype``), and compared with the
signature stored at that program's previous compile. The difference
goes out as a schema-validated ``kind: recompile`` record, the
``roofline_recompiles_total`` counter and a warning, and is kept for the
``xla_compile_counter`` test fixture, whose failing assertion prints it.
"""

from __future__ import annotations

import collections
import logging
from typing import Any, Dict, List, Optional, Tuple

from . import metrics as obs_metrics

logger = logging.getLogger(__name__)

Signature = Tuple[Tuple[str, str], ...]

# the newest records, process-wide (tests/conftest.py prints them)
_recent_recompiles: collections.deque = collections.deque(maxlen=16)


def recent_recompiles() -> List[Dict[str, Any]]:
    return list(_recent_recompiles)


def _leaf_desc(leaf: Any) -> str:
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is None or dtype is None:
        return f"py:{type(leaf).__name__}"
    return f"{dtype}[{','.join(str(d) for d in shape)}]"


def dispatch_signature(args: Any) -> Signature:
    """(tree path, ``dtype[shape]``) of every leaf of a call's arguments."""
    import jax
    flat = jax.tree_util.tree_flatten_with_path(args)[0]
    return tuple((jax.tree_util.keystr(path), _leaf_desc(leaf))
                 for path, leaf in flat)


def _changed(old: Dict[str, str], new: Dict[str, str]
             ) -> List[Dict[str, Any]]:
    rows = [{"arg": path, "was": old.get(path), "now": desc}
            for path, desc in new.items() if old.get(path) != desc]
    rows += [{"arg": path, "was": desc, "now": None}
             for path, desc in old.items() if path not in new]
    return rows


class RecompileTracker:
    """One per engine or scheduler: the signature each program last
    compiled at, and how often it has compiled. It takes a program's
    first dispatch through it to fire a compile event (each engine and
    scheduler jits its own closures, and a load from the persistent
    cache fires the event too): a program that came to it already
    compiled would have its first recompile read as the expected first
    compile."""

    def __init__(self) -> None:
        self._sigs: Dict[str, Signature] = {}
        self._compiles: Dict[str, int] = {}

    def observe(self, program: str, args: Any,
                compiles: int) -> Optional[Dict[str, Any]]:
        """Call after every dispatch with the backend compiles it
        triggered. Returns the record when ``program`` compiled again,
        None on its first compile and on every call that compiled
        nothing (which walks no leaf)."""
        if compiles <= 0:
            return None
        sig = dispatch_signature(args)
        prev = self._sigs.get(program)
        self._sigs[program] = sig
        total = self._compiles.get(program, 0) + int(compiles)
        self._compiles[program] = total
        if prev is None:
            return None   # the expected first compile
        changed = _changed(dict(prev), dict(sig))
        note = None if changed else (
            "no abstract-shape change — cache miss from a new callable, "
            "jit options, or sharding change")
        rec = {"program": str(program), "compiles": int(compiles),
               "total_compiles": int(total), "expected": 1,
               "changed": changed, "note": note}
        from .. import mlops
        mlops._emit("recompile", rec)
        obs_metrics.record_recompile(program)
        _recent_recompiles.append(rec)
        logger.warning(
            "recompile forensics[%s]: %d compile(s) past the pinned "
            "expectation; changed: %s", program, compiles, changed or note)
        return rec
