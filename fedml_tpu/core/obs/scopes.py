"""Device time by the program's own scopes.

One closed vocabulary of ``jax.named_scope`` names over the whole round
program (:data:`SCOPES`), and the table that says which of them each
instruction of a compiled program belongs to (:func:`table`). A scope is
the program's text, not a recording: :func:`scope` costs something only
while Python traces a program, leaves no span and no ring entry, changes
no instruction of the compiled round (only ``metadata={op_name=...}``), and
so stays on with ``obs_tracing: false``.

An operation belongs to its INNERMOST scope of the vocabulary, so the
scopes partition a round: ``lora`` time inside ``attn.full`` is ``lora``'s.

The table is made on demand, after the window of a traced run, from the
program's own compiled text: the dispatch seam (``TPUSimulator._traced``)
hands :func:`note_program` the jitted function and the abstract arguments
of a dispatch that compiled, and :func:`table` lowers and compiles them
again, which JAX answers from its caches with the executable that ran.
Joined by instruction name to a profiler trace's device events
(``benchmarks/harness/scope_time.py``, ``benchmarks/tools/scope_table.py``)
it gives device time by scope. No FLOPs, no bytes, no peak, no prediction.
"""

from __future__ import annotations

import logging
import re
import time
import weakref
from typing import Any, Dict, List, Optional

from . import trace as obs_trace

logger = logging.getLogger(__name__)

# scope -> what it wraps (its module's WHOLE call)
SCOPES = (
    "engine.slot",        # run_slot: a client's data and state slices
    "engine.accumulate",  # the weighted sum of a client's update and metrics
    "engine.server",      # finish: psums, the division, central DP, server
    "local.batch",        # epoch order, the batch gather, the step's keys
    "local.grad",         # value_and_grad: what no model scope covers
    "local.update",       # grad_transform, the optimizer, the metric sums
    "cv.conv",            # ResNet's convolutions, shortcut projections too
    "cv.norm",            # GroupNorm, ReLU, the residual add
    "cv.head",            # pooling and the classifier
    "embed",              # the token embedding
    "attn.full",          # a whole attention module of that kind: the
    "attn.window",        # projections, rotary, jnp.repeat of grouped
    "attn.latent",        # heads, layout copies, the kernels, the output
    "attn.linear",        # projection
    "attn.ssm",           # a whole Mamba-2 mixer: projections, convolution,
                          # gates, the SSD kernels, the grouped norm
    "attn.sparse",        # a whole sparse attention module but its indexer
    "attn.index",         # the indexer: its projections, norm, rotary, the
                          # index scores and the selection
    "mlp",                # the dense MLP and the shared expert
    "moe.route",          # the router, top-k, the gates
    "moe.experts",        # the plan, the grouped products, the slot sums
    "moe.latent",         # the projections into and out of the experts' latent
    "norm",               # a decoder layer's two RMSNorms and residual adds
    "head",               # ln_f, the head product, float32 logits, the loss
    "lora",               # the rank-r side path, inside attn.* / mlp
)
_VOCABULARY = frozenset(SCOPES)


def scope(name: str):
    """``jax.named_scope(name)`` for a name of :data:`SCOPES`; any other
    name is a ``ValueError`` (the vocabulary is closed: a reader that sums
    scopes must know them all)."""
    if name not in _VOCABULARY:
        raise ValueError(f"unknown scope {name!r}: core/obs/scopes.SCOPES "
                         f"is the whole vocabulary")
    import jax
    return jax.named_scope(name)


_WRAPPED = re.compile(r"^[\w.\-]*\((.*)\)$")


def _bare(component: str) -> str:
    """``transpose(jvp(moe.experts))`` -> ``moe.experts``: JAX wraps the
    name-stack entry a transform meets first, one wrapper a transform."""
    while True:
        m = _WRAPPED.match(component)
        if m is None:
            return component
        component = m.group(1)


def scope_of(op_name: str) -> Optional[str]:
    """The innermost vocabulary scope of an operation's ``op_name`` path
    (``jit(round)/.../transpose(jvp(CausalLM))/layer_1/moe/moe.experts/
    cond/branch_1_fun/...``), through JAX's wrappers; None without one."""
    for component in reversed(op_name.split("/")):
        name = _bare(component)
        if name in _VOCABULARY:
            return name
    return None


_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'\bop_name="((?:[^"\\]|\\.)*)"')
# after the produced shape (which ends in ], } or a tiling's parenthesis):
# the opcode and its operand list
_OPERANDS = re.compile(r"[\]\)\}]\s[a-z][a-z\-]*\(([^()]*)\)")


def parse(hlo_text: str) -> Dict[str, Optional[str]]:
    """{instruction name: scope or None} of an HLO module's text, every
    computation's instructions (a fusion by the metadata XLA gave the
    fusion instruction itself; its fused computation's are listed too and
    never run as events of their own). An instruction whose ``op_name``
    names no scope takes the scope of the first operand that has one,
    through chains of such instructions, else (a prefetched weight: the
    chain ends at a parameter) of the first user that has one; None where
    neither side leads to a scope. Two kinds need it: what the compiler
    made itself and gave no ``op_name`` (the asynchronous copies and slices
    of memory-space assignment, layout copies, bitcasts: what they move is
    some scope's data), and what the TPU compiler inlined out of a nested
    ``jit`` inside a conditional's branch, whose ``op_name`` it leaves cut
    at the inner function (``jit(_pull_back)/gather``, seen in the expert
    layer's backward pass; the CPU compiler writes the whole path)."""
    own: Dict[str, Optional[str]] = {}
    operands: Dict[str, List[str]] = {}
    users: Dict[str, List[str]] = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            continue
        name = m.group(1)
        op = _OP_NAME.search(line, m.end())
        own[name] = scope_of(op.group(1)) if op else None
        args = _OPERANDS.search(line, m.end())
        if args is not None:
            operands[name] = [a.strip().lstrip("%")
                              for a in args.group(1).split(",")]
            for arg in operands[name]:
                users.setdefault(arg, []).append(name)
    for edges in (operands, users):
        for name in [n for n, found in own.items() if found is None]:
            own[name] = _along(name, own, edges, set())
    return own


def _along(name, own, edges, seen) -> Optional[str]:
    """The scope of the first instruction with one that ``edges`` reach
    from ``name``, depth first, through instructions without one."""
    for nxt in edges.get(name, ()):
        if nxt in seen or nxt not in own:
            continue
        seen.add(nxt)
        found = own[nxt] or (_along(nxt, own, edges, seen)
                             if len(seen) < 64 else None)
        if found is not None:
            return found
    return None


_LOCATION = re.compile(r'loc\("([^"]*)"')


def _lowered_scopes(lowered) -> frozenset:
    """The vocabulary scopes in a lowered module's locations (a name
    location holds a stretch of the name stack, ``"engine.slot/while/body/
    local.grad/jvp(CausalLM)/layer_1/moe/moe.experts/sub"``)."""
    text = lowered.as_text(debug_info=True)
    return frozenset(
        n for path in set(_LOCATION.findall(text))
        for n in map(_bare, path.split("/")) if n in _VOCABULARY)


class _Program:
    __slots__ = ("fn", "specs", "table", "build")

    def __init__(self, fn, specs):
        self.fn, self.specs = weakref.ref(fn), specs
        self.table: Optional[Dict[str, Optional[str]]] = None
        self.build: Optional[Dict[str, Any]] = None


# program name ("round", "fused_rounds", ...) -> the newest dispatch of it
# that compiled; the jitted function is held weakly (an engine that was
# freed leaves no round program behind here)
_programs: Dict[str, _Program] = {}


def _spec(leaf):
    import jax
    if not isinstance(leaf, jax.Array):
        return leaf
    # an uncommitted array (a scalar the host made) is lowered without a
    # sharding of its own: give it none here, or the caches miss
    return jax.ShapeDtypeStruct(
        leaf.shape, leaf.dtype, weak_type=leaf.weak_type,
        sharding=leaf.sharding if leaf.committed else None)


def note_program(name: str, fn, args, compiled: bool) -> None:
    """The dispatch seam's call, after every dispatch of ``fn(*args)``
    under ``name``: keeps the jitted function (weakly) and the arguments'
    ``ShapeDtypeStruct``s with their shardings when the dispatch compiled
    or is the first of this function under its name; one dictionary lookup
    on every other dispatch; nothing with ``obs_tracing: false``. A donated
    array still knows its shape, dtype and sharding."""
    if not obs_trace.is_enabled():
        return
    prog = _programs.get(name)
    if not compiled and prog is not None and prog.fn() is fn:
        return
    import jax
    _programs[name] = _Program(fn, jax.tree_util.tree_map(_spec, args))


def _compiled_past_the_caches(lowered) -> str:
    """The text of ``lowered`` compiled anew: the persistent cache is
    switched off for this call (its decision is memoised, hence the
    resets), and a compile with compiler options of its own is neither
    answered from nor kept in the lowering's in-memory executable."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        return lowered.compile(
            compiler_options={"xla_dump_disable_metadata": False}).as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def table(name: str = "round") -> Optional[Dict[str, Optional[str]]]:
    """{HLO instruction name: scope or None} of the program last noted
    under ``name``, made once and kept; None, never a raise, where no
    program was noted (``obs_tracing: false``), the engine is gone or the
    lowering fails.

    ``fn.lower(*specs).compile()`` is no second Python trace and no second
    compile: JAX's caches hand back the executable that ran. One trap: the
    persistent cache's key is taken with debug information stripped, and
    scopes ARE debug information, so an executable loaded from it may have
    been compiled from another tree's lowering of the same instructions
    and carry that tree's ``op_name``s. So the scopes in the compiled
    text's metadata must be the scopes in this lowering's locations; if
    not, this one program is compiled again past the caches and that text
    is read (same instructions, same compiler: the same instruction
    names). :func:`last_build` says what it cost."""
    prog = _programs.get(name)
    if prog is None:
        return None
    if prog.table is not None:
        return prog.table
    fn = prog.fn()
    if fn is None:
        return None
    t0 = time.perf_counter()
    try:
        lowered = fn.lower(*prog.specs)
        want = _lowered_scopes(lowered)
        found = parse(lowered.compile().as_text())
        build = {"stale": frozenset(filter(None, found.values())) != want,
                 "first_s": time.perf_counter() - t0}
        if build["stale"]:
            found = parse(_compiled_past_the_caches(lowered))
    except Exception:  # a reader's question must not take down a run
        logger.exception("scope table[%s]: lowering or compiling failed",
                         name)
        return None
    build.update(seconds=time.perf_counter() - t0, instructions=len(found),
                 scopes=sorted(set(filter(None, found.values()))))
    logger.info("scope table[%s]: %s", name, build)
    prog.table, prog.build = found, build
    return found


def last_build(name: str = "round") -> Optional[Dict[str, Any]]:
    """What :func:`table` cost for ``name``: ``seconds`` in all,
    ``first_s`` of them for the cached executable's text, ``stale``
    (whether that text carried another tree's scopes and the program was
    compiled again), ``instructions``, ``scopes``; None before a table."""
    prog = _programs.get(name)
    return None if prog is None else prog.build
