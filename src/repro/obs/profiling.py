"""Profiling hooks: jax.profiler annotations + opt-in xplane trace dumps.

Thin wrappers so the serving/bench layers never import `jax.profiler`
directly (the module is optional in stripped builds) and never pay the
annotation cost unless a dump directory armed the session:

  annotate(name)        TraceAnnotation context — labels the enclosing
                        host region in the xplane timeline, nesting the
                        device dispatches it issues under it.
  trace_session(dir)    jax.profiler.trace context writing an xplane dump
                        under `dir`; `None` -> no-op nullcontext, so call
                        sites wrap unconditionally.

`LAYER_SCOPES` lists the `jax.named_scope`s that divide the fused window
(`SmartPQ.run_window` / `step`) into its layers.  A scope is metadata only:
it lands in each HLO instruction's `op_name` (`.../pq.schedule/cond/
branch_0_fun/pq.schedule.hier/...`) and changes no program.  An op belongs
to the innermost `pq.*` scope of its `op_name`; a schedule branch's scope
(`pq.schedule.<schedule name, lower case>`) rolls up into `pq.schedule`.
Each kernel dispatch of `repro.kernels.ops` adds a `kernel.<kernel>.<arm>`
scope, orthogonal to the layers.
"""

from __future__ import annotations

import contextlib
from typing import ContextManager, Optional

LAYER_SCOPES = (
    "pq.presort",  # run_window: float sanitising, the op-log sort
    "pq.decide",  # step: batch stats, features, tree, mode select
    "pq.eliminate",  # step: elimination split and merge
    "pq.insert",  # step: routing and the tiered insert
    "pq.refill",  # step: the guarded head refill
    "pq.schedule",  # step: the mode switch and its branches
    "pq.compact",  # local.compact_tail, from insert or refill
)


def annotate(name: str) -> ContextManager[None]:
    """A jax.profiler.TraceAnnotation, or a nullcontext when the profiler
    is unavailable."""
    try:
        from jax.profiler import TraceAnnotation
    except ImportError:  # pragma: no cover — stripped jax builds
        return contextlib.nullcontext()
    return TraceAnnotation(name)


def trace_session(dump_dir: Optional[str]) -> ContextManager[None]:
    """Profiler session writing an xplane dump under `dump_dir`; no-op
    when `dump_dir` is None (the default serving configuration)."""
    if dump_dir is None:
        return contextlib.nullcontext()
    try:
        from jax.profiler import trace
    except ImportError:  # pragma: no cover — stripped jax builds
        return contextlib.nullcontext()
    return trace(str(dump_dir))


__all__ = ["LAYER_SCOPES", "annotate", "trace_session"]
