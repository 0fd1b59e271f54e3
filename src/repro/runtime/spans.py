"""The engine's host spans, on the profiler's own clock.

A span is a `jax.profiler.TraceAnnotation` (a TraceMe): while a profiler
session is open it lands in the same `.xplane.pb` as the device planes,
on the calling thread, nested in whatever span that thread has open;
with no session it costs about what entering a `contextlib.nullcontext`
costs.  Spans are always emitted.  Their stats are small integers or
short strings.

    nshedb.query            Executor.run / run_compiled   run, plan
      nshedb.admit          compile + static verify       run
        nshedb.compile
        nshedb.verify
      nshedb.stage:<label>  a DAG stage, labelled as in ExecReport.history
        nshedb.circuit:<k>  one stacked comparison circuit (eq | lt)  lanes
          nshedb.he:<op>    one BFVBackend op               blocks

The spans of one query share its `run` id.  Device programs are named
apart, by `jax.named_scope` inside the jitted HE programs (`he.*`, see
core/bfv.py), which costs nothing at run time.
"""
from __future__ import annotations

import itertools

import jax

QUERY = "nshedb.query"
ADMIT = "nshedb.admit"
COMPILE = "nshedb.compile"
VERIFY = "nshedb.verify"
STAGE = "nshedb.stage:"
CIRCUIT = "nshedb.circuit:"
HE = "nshedb.he:"

_runs = itertools.count(1)


def next_run() -> int:
    """A fresh query id, unique in this process."""
    return next(_runs)


def span(name: str, **stats) -> jax.profiler.TraceAnnotation:
    """`with span(name, **stats):` times the block as a host span."""
    return jax.profiler.TraceAnnotation(name, **stats)
