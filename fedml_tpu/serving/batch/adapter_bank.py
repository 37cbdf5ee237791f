"""Multi-LoRA adapter bank: named per-user/per-silo adapters resident as
ONE stacked pytree the jitted decode step gathers from.

The federated-personalization loop this closes: ``llm/federated.py``
produces per-silo LoRA adapter artifacts (kilobytes each); the bank loads
them side by side over one frozen base model, and every request selects
its adapter by name — the selection becomes a per-slot integer index, the
gather happens inside the compiled step, and serving a new silo's users
costs one bank row, not a model replica (S-LoRA's economics).

The stack is CAPACITY-padded: leaves are ``[capacity, ...]`` from
construction, so registering adapter #2 through #capacity never changes
the compiled step's input shapes (compile-once holds across bank growth).
Index 0 is always the zero adapter — requests with no adapter get the
base model exactly.

Hot-swap (the federated adapter flywheel): :meth:`AdapterBank.swap`
publishes a NEW version of a named adapter by writing a FRESH row and
repointing the name — never by overwriting the live row — so requests
already in flight (which resolved the name to a row index at submit and
pinned it via :meth:`retain_row`) keep the exact version they started
with; the retired row returns to the free pool when its last pin drops.
:meth:`watch_dir` polls a ``save_adapter_artifacts`` export directory
and swaps in changed/new adapters live: a fresh federated round's export
goes live as a row write — zero restart, zero recompile (the capacity
padding keeps the stacked pytree's shapes constant; only a host→device
refresh of the stack happens).
"""

from __future__ import annotations

import logging
import os
import threading
from typing import Any, Dict, List, Optional

import jax
import numpy as np

PyTree = Any
logger = logging.getLogger(__name__)

BASE_ADAPTER = "base"


class AdapterBank:
    """Named LoRA adapters over one base model.

    ``template``: any adapter tree with the served model's LoRA structure
    (``lora_init`` output or a loaded artifact) — defines the leaf shapes;
    its values are NOT registered. ``capacity``: maximum adapters
    (including the reserved zero adapter at index 0)."""

    def __init__(self, template: PyTree, alpha: float = 16.0,
                 capacity: int = 64):
        import jax.numpy as jnp

        leaves, self._treedef = jax.tree_util.tree_flatten(template)
        if not leaves:
            raise ValueError("adapter template has no leaves")
        self.capacity = int(capacity)
        if self.capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.alpha = float(alpha)
        # rank from any lora_a leaf: [d_in, r]
        self.rank = int(leaves[0].shape[-1] if leaves[0].ndim == 2 else 0)
        self._lock = threading.Lock()
        self._names: Dict[str, int] = {BASE_ADAPTER: 0}
        # host mirror [capacity, ...] per leaf; row 0 stays zero
        self._host: List[np.ndarray] = [
            np.zeros((self.capacity,) + tuple(l.shape), np.float32)
            for l in leaves]
        self._stack = None   # lazily device-put pytree
        self._jnp = jnp
        # hot-swap bookkeeping: per-row in-flight pins, rows whose name
        # moved on (reusable once unpinned), and the watcher thread
        self._row_refs: Dict[int, int] = {}
        self._retired: set = set()
        self.swaps = 0
        self._watch_thread: Optional[threading.Thread] = None
        self._watch_stop = threading.Event()

    @property
    def scale(self) -> float:
        """The side path's ``alpha / rank`` factor."""
        r = max(self.rank, 1)
        return self.alpha / r

    def __len__(self) -> int:
        with self._lock:
            return len(self._names)

    def names(self) -> List[str]:
        with self._lock:
            return sorted(self._names, key=self._names.get)

    def _check_leaves(self, name: str, adapter: PyTree) -> List[np.ndarray]:
        leaves = jax.tree_util.tree_leaves(adapter)
        if len(leaves) != len(self._host):
            raise ValueError(
                f"adapter {name!r}: {len(leaves)} leaves != template's "
                f"{len(self._host)}")
        arrs = []
        for host, leaf in zip(self._host, leaves):
            arr = np.asarray(leaf, np.float32)
            if arr.shape != host.shape[1:]:
                raise ValueError(
                    f"adapter {name!r}: leaf shape {arr.shape} != "
                    f"template {host.shape[1:]} (same targets and "
                    "rank required)")
            arrs.append(arr)
        return arrs

    def _next_row_locked(self) -> int:
        """Smallest unused, unretired row (row 0 reserved). Retired rows
        rejoin the pool only when their last in-flight pin drops."""
        in_use = set(self._names.values()) | self._retired
        for r in range(1, self.capacity):
            if r not in in_use:
                return r
        raise RuntimeError(
            f"adapter bank full ({self.capacity}); raise "
            "serving_max_adapters")

    def add(self, name: str, adapter: PyTree) -> int:
        """Register (or replace IN PLACE) a named adapter; returns its
        index. In-place replacement mutates the live row — use
        :meth:`swap` when requests may be in flight on the old
        version."""
        arrs = self._check_leaves(name, adapter)
        with self._lock:
            if name == BASE_ADAPTER:
                raise ValueError(f"{BASE_ADAPTER!r} is the reserved zero "
                                 "adapter")
            idx = self._names.get(name)
            if idx is None:
                idx = self._next_row_locked()
                self._names[name] = idx
            for host, arr in zip(self._host, arrs):
                host[idx] = arr
            self._stack = None
        return idx

    def swap(self, name: str, adapter: PyTree) -> int:
        """Hot-swap: publish a new version of ``name`` on a FRESH row
        and repoint the name — in-flight requests pinned to the old row
        keep the version they started with; the old row is retired and
        reused only once its last pin drops. A previously unknown name
        is simply added. Returns the (new) index."""
        arrs = self._check_leaves(name, adapter)
        with self._lock:
            if name == BASE_ADAPTER:
                raise ValueError(f"{BASE_ADAPTER!r} is the reserved zero "
                                 "adapter")
            old = self._names.get(name)
            idx = self._next_row_locked()
            for host, arr in zip(self._host, arrs):
                host[idx] = arr
            self._names[name] = idx
            if old is not None and old != 0:
                if self._row_refs.get(old, 0) > 0:
                    self._retired.add(old)
                # unpinned old row: implicitly free (not named, not
                # retired) — _next_row_locked can hand it out again
            self.swaps += 1
            self._stack = None
        from ...core.obs import metrics as obs_metrics
        obs_metrics.record_llm_adapter_swap(name)
        logger.info("adapter bank: hot-swapped %r -> row %d (old row "
                    "%s)", name, idx, old)
        return idx

    def acquire(self, name: str) -> int:
        """Resolve a name to its row AND pin it, under ONE lock hold —
        a separate ``index()`` + ``retain_row()`` pair leaves a window
        where a concurrent swap retires-and-reuses the resolved row and
        the request decodes someone else's weights. Pair with
        :meth:`release_row`. Unknown names raise like :meth:`index`."""
        with self._lock:
            idx = self._names.get(str(name))
            if idx is None:
                loaded = sorted(self._names, key=self._names.get)
                raise KeyError(f"unknown adapter {name!r}; loaded: "
                               f"{loaded}")
            if idx > 0:
                self._row_refs[idx] = self._row_refs.get(idx, 0) + 1
            return idx

    def retain_row(self, idx: int) -> None:
        """Pin a row for an in-flight request (the engine calls this at
        submit): a pinned retired row is never reused. For pinning BY
        NAME use :meth:`acquire` — it closes the resolve-then-pin race
        against a concurrent hot-swap."""
        i = int(idx)
        if i <= 0:
            return   # the zero adapter is immutable
        with self._lock:
            self._row_refs[i] = self._row_refs.get(i, 0) + 1

    def release_row(self, idx: int) -> None:
        i = int(idx)
        if i <= 0:
            return
        with self._lock:
            n = self._row_refs.get(i, 0)
            if n <= 1:
                self._row_refs.pop(i, None)
                self._retired.discard(i)   # now reusable
            else:
                self._row_refs[i] = n - 1

    # --- watched hot-swap ---------------------------------------------------
    def watch_dir(self, manifest_dir: str, poll_s: float = 2.0,
                  swap_existing: bool = False) -> None:
        """Poll a ``save_adapter_artifacts`` dir and hot-swap changed or
        new adapters live. The initial scan only RECORDS mtimes (the
        bank was typically just loaded from this dir) unless
        ``swap_existing``; every subsequent change to the manifest or an
        artifact file triggers :meth:`swap` for the affected names.
        Half-written exports are tolerated (the exporter writes
        atomically via os.replace; a transient read error just waits for
        the next poll)."""
        if self._watch_thread is not None and self._watch_thread.is_alive():
            raise RuntimeError("already watching an adapter dir")
        self._watch_stop.clear()
        seen: Dict[str, float] = {} if swap_existing \
            else self._scan_mtimes(manifest_dir)

        def loop() -> None:
            while not self._watch_stop.wait(float(poll_s)):
                try:
                    self._poll_once(manifest_dir, seen)
                except Exception:  # noqa: BLE001 — watcher must survive
                    logger.exception("adapter watch poll failed (will "
                                     "retry)")

        self._watch_thread = threading.Thread(
            target=loop, daemon=True, name="llm-adapter-watch")
        self._watch_thread.start()
        logger.info("adapter bank: watching %s every %.1fs",
                    manifest_dir, float(poll_s))

    @staticmethod
    def _scan_mtimes(manifest_dir: str) -> Dict[str, float]:
        out: Dict[str, float] = {}
        try:
            import json
            with open(os.path.join(manifest_dir, "manifest.json")) as f:
                manifest = json.load(f)
            for name, fname in (manifest.get("adapters") or {}).items():
                try:
                    out[str(name)] = os.path.getmtime(
                        os.path.join(manifest_dir, fname))
                except OSError:
                    pass
        except Exception:  # noqa: BLE001 — nothing exported yet
            pass
        return out

    def _poll_once(self, manifest_dir: str, seen: Dict[str, float]) -> None:
        import json
        with open(os.path.join(manifest_dir, "manifest.json")) as f:
            manifest = json.load(f)
        changed = []
        for name, fname in (manifest.get("adapters") or {}).items():
            try:
                mtime = os.path.getmtime(os.path.join(manifest_dir, fname))
            except OSError:
                continue   # export in progress
            if seen.get(str(name)) != mtime:
                changed.append((str(name), fname, mtime))
        if not changed:
            return
        from ...serving import load_model
        for name, fname, mtime in changed:
            tree = load_model(os.path.join(manifest_dir, fname))
            self.swap(name, tree)
            seen[name] = mtime

    def stop_watch(self) -> None:
        self._watch_stop.set()
        th = self._watch_thread
        if th is not None:
            th.join(timeout=5.0)
            self._watch_thread = None

    def index(self, name: Optional[str]) -> int:
        """Name → bank index; ``None`` → the zero adapter. Unknown names
        raise — serving a user the WRONG personalization silently is the
        one failure mode a personalization gateway must not have."""
        if name is None:
            return 0
        with self._lock:
            idx = self._names.get(str(name))
        if idx is None:
            raise KeyError(f"unknown adapter {name!r}; loaded: "
                           f"{self.names()}")
        return idx

    def has(self, name: str) -> bool:
        with self._lock:
            return str(name) in self._names

    def stack(self) -> PyTree:
        """The resident ``[capacity, ...]`` device pytree (rebuilt lazily
        after adds; the capacity padding keeps its shapes constant)."""
        with self._lock:
            if self._stack is None:
                self._stack = jax.tree_util.tree_unflatten(
                    self._treedef,
                    [self._jnp.asarray(h) for h in self._host])
            return self._stack

    @classmethod
    def from_artifacts(cls, manifest_dir: str, alpha: float = 16.0,
                       capacity: int = 64) -> "AdapterBank":
        """Build a bank from a ``save_adapter_artifacts`` directory
        (manifest.json + one msgpack artifact per named adapter — the
        layout ``llm/federated.py`` exports per silo)."""
        from ...llm.federated import load_adapter_artifacts
        adapters = load_adapter_artifacts(manifest_dir)
        if not adapters:
            raise ValueError(f"no adapters in {manifest_dir}")
        template = next(iter(adapters.values()))
        # +2: the reserved zero row AND the served artifact's own adapter,
        # which CausalLMPredictor registers as "default" after loading —
        # a manifest that exactly fills `capacity` must not crash there
        bank = cls(template, alpha=alpha,
                   capacity=max(capacity, len(adapters) + 2))
        for name, tree in adapters.items():
            bank.add(name, tree)
        logger.info("adapter bank: loaded %d adapters from %s",
                    len(adapters), manifest_dir)
        return bank
