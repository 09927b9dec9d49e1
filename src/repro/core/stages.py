"""Names of the simulated step's stages (device) and of an engine call's
phases (host), for the profiler.

A device stage is a `jax.named_scope`: XLA keeps the scope path in each
op's `op_name` metadata, so a profiler trace puts every device op under
the stage that issued it (a fusion under the stage of its root op).  A
host phase is a `jax.profiler.TraceAnnotation` span; its keyword
arguments become stats on the trace event, and it lands on the device
trace's clock, so an idle gap on the device can be put down to the phase
the host was in.  All spans of one engine call carry the same `call` id.

Neither costs anything that changes a result: a scope is metadata only,
and a span is one check of whether a profiler is recording.  On the
numpy backend both are `contextlib.nullcontext()`, and jax is not
imported for them.  docs/ARCHITECTURE.md ("Tracing a run") says what
each name covers.
"""
from __future__ import annotations

import contextlib
import itertools

import numpy as np

#: device stages of the scanned step (jax.named_scope names)
STAGES = ("lark_node_advance", "lark_rank_gather", "lark_step_eval",
          "lark_roster", "lark_node_counts", "lark_protocols",
          "lark_latency")

#: host phases of an engine call (TraceAnnotation names)
SPANS = ("lark.call", "lark.setup", "lark.chunk_program", "lark.dispatch",
         "lark.drain", "lark.stop_test")

_calls = itertools.count()


def next_call() -> int:
    """A fresh id for the spans of one engine call."""
    return next(_calls)


def stage(xp, name: str):
    """The named scope of one step stage under the array namespace `xp`
    (a null context under numpy)."""
    if name not in STAGES:
        raise ValueError(f"unknown stage {name!r}; expected one of {STAGES}")
    if xp is np:
        return contextlib.nullcontext()
    import jax
    return jax.named_scope(name)


def span(backend: str, name: str, **args):
    """The host span of one engine-call phase, with `args` as its stats
    (a null context on the numpy backend)."""
    if name not in SPANS:
        raise ValueError(f"unknown span {name!r}; expected one of {SPANS}")
    if backend == "numpy":
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name, **args)


def annotate(active_span, **args) -> None:
    """Add stats known only at a span's end (no-op on a null context)."""
    if active_span is not None:
        active_span.set_metadata(**args)
