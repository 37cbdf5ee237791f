"""Device time by the program's own scopes: the trace's device events joined
to the scope table the program makes from its compiled round.

``ctx["trace"]["op_calls"]`` holds device seconds keyed by a name whose
first token is the HLO instruction's own name (``fusion.12 fusion
bf16[...]``); ``fedml_tpu.core.obs.scopes.table()`` says which scope of the
program's closed vocabulary each instruction of the round program belongs
to (its innermost one). Joined once a run, kept on ``ctx``:
``{scope: seconds}`` plus ``unscoped`` (an instruction the table has under
no scope) and ``unjoined`` (an event whose instruction the table lacks: the
small programs between rounds). None where the trace or the table is
missing: a program without ``core/obs/scopes.py`` (the parent of PR 36), a
program run with ``obs_tracing: false``.

With ``BENCH_KEEP_TRACE`` set the table and the sums are also written into
the kept trace's directory (``scopes.json``, ``scope_ms.json``), where
``tools/scope_table.py`` reads them.
"""

import json
import os
import time

from harness import manifest

UNSCOPED, UNJOINED = "unscoped", "unjoined"
# scopes summed into one metric (BENCHMARK.json: scope_engine_ms)
ENGINE = ("engine.slot", "engine.accumulate", "engine.server", "local.batch",
          "local.update")


def join(op_calls, table):
    """{scope | "unscoped" | "unjoined": device seconds} of ``op_calls``
    (``{name: (events, seconds)}``) under ``table`` (``{instruction name:
    scope or None}``)."""
    out = {UNSCOPED: 0.0, UNJOINED: 0.0}
    for key, (_, seconds) in op_calls.items():
        name = key.split(" ", 1)[0]
        scope = (table[name] or UNSCOPED) if name in table else UNJOINED
        out[scope] = out.get(scope, 0.0) + seconds
    return out


def program_table():
    """The round program's scope table, or None (with what it cost, for the
    run's own output)."""
    try:
        from fedml_tpu.core.obs import scopes
    except ImportError:
        return None, None
    t = time.perf_counter()
    table = scopes.table("round")
    build = dict(scopes.last_build("round") or {},
                 table_s=time.perf_counter() - t)
    return table, build


def by_scope(ctx):
    """``join`` of this run's trace and table, made once; None without
    either."""
    if "scope_seconds" not in ctx:
        ctx["scope_seconds"] = None
        trace = ctx.get("trace")
        table, build = program_table() if trace else (None, None)
        if table is not None:
            ctx["scope_seconds"] = join(trace["op_calls"], table)
            _report(ctx, table, build)
    return ctx["scope_seconds"]


def rounds_kept(ctx):
    """The traced rounds whose device events the profile holds: all of
    them, unless the profiler's cap on events cut the trace short (about
    6.2 million: the one-chip ResNet cell's two rounds make 8.5 million,
    so its profile ends 3.87 s into a 5.31 s window); then the kept device
    window over a round's period on the host's clock. None without a
    traced round."""
    rounds = ctx.get("traced_rounds")
    if not rounds or not ctx.get("traced_seconds"):
        return rounds or None
    kept = ctx["trace"]["window_s"] * rounds / ctx["traced_seconds"]
    return rounds if kept > rounds - 0.5 else kept


def _report(ctx, table, build):
    rounds = rounds_kept(ctx) or 1
    ms = {k: 1e3 * v / rounds for k, v in ctx["scope_seconds"].items()}
    print(json.dumps({"info": "scopes", "build": build, "ms_a_round": ms}),
          flush=True)
    if os.environ.get("BENCH_KEEP_TRACE"):
        kept = os.path.join(manifest.REPO, ".bench_trace", ctx["cell"].name)
        if os.path.isdir(kept):
            with open(os.path.join(kept, "scopes.json"), "w") as f:
                json.dump(table, f)
            with open(os.path.join(kept, "scope_ms.json"), "w") as f:
                json.dump({"rounds_kept": rounds, "ms_a_round": ms}, f,
                          indent=1)


def ms_a_round(ctx, *scopes):
    """Device milliseconds a traced round in ``scopes`` together (their
    seconds over the rounds the profile holds); None without a table, a
    trace or a traced round."""
    seconds = by_scope(ctx)
    if seconds is None or not ctx.get("traced_rounds"):
        return None
    return 1e3 * sum(seconds.get(s, 0.0) for s in scopes) / rounds_kept(ctx)
