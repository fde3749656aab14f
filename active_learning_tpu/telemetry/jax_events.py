"""The program's ``jax.monitoring`` listeners, in one place.

Two things are read off JAX's own monitoring events, both registered once
when this module is imported (the experiment driver imports it):

  * **Persistent compilation cache traffic** of this process: a hit is an
    executable read back from the cache directory, a miss one compiled
    and written there.  The driver journals the counts at every round
    end, and a window's misses are a per-layer metric of the benchmark:
    whether a second process really found the first one's programs is
    otherwise invisible.
  * **Compile stages as spans** (``jax/compile``, args ``stage`` and
    ``fun_name``): tracing to a jaxpr (``trace``), lowering to an MLIR
    module (``lower``) and the backend compile, or its load from the
    persistent cache (``compile``).  JAX times each stage in
    ``dispatch.log_elapsed_time`` and reports its ``time.time()`` start
    and end once it has ended; the tracer records it retroactively under
    the span open at that moment, ending then on the record's
    ``perf_counter`` clock and lasting what the two stamps say (no offset
    between the two clocks to drift).  Only a stage that ends outside any
    trace is recorded: every jnp function is a jit of its own, so tracing
    one program traces hundreds of nested functions, each inside the
    outer trace's stage (on a TPU v5e, 92-235 stages of a benchmark
    cell's run ended outside a trace, against 3,600-8,200 in all, for the
    same seconds).  With the recorder off a stage costs one flag test.
"""

from __future__ import annotations

import time

import jax
import jax.core

from . import spans as spans_lib

_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
                 "/jax/compilation_cache/cache_misses": "misses"}
_cache_counts = {"hits": 0, "misses": 0}

COMPILE_SPAN = "jax/compile"
# The events ``dispatch.log_elapsed_time`` reports, by stage.
STAGES = {"/jax/core/compile/jaxpr_trace_duration": "trace",
          "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
          "/jax/core/compile/backend_compile_duration": "compile"}


def _count_cache_event(event: str, **_kwargs) -> None:
    key = _CACHE_EVENTS.get(event)
    if key is not None:
        _cache_counts[key] += 1


def compilation_cache_counts() -> dict:
    """{"hits", "misses"} of the persistent compilation cache in this
    process so far."""
    return dict(_cache_counts)


def _stage_ended(event: str, start_time: float, end_time: float,
                 **kwargs) -> None:
    stage = STAGES.get(event)
    if stage is None:
        return
    tracer = spans_lib.get_tracer()
    if tracer.enabled and jax.core.trace_ctx.is_top_level():
        t1 = time.perf_counter()
        tracer.complete(COMPILE_SPAN, t1 - (end_time - start_time), t1,
                        {"stage": stage, "fun_name": kwargs.get("fun_name")})


jax.monitoring.register_event_listener(_count_cache_event)
jax.monitoring.register_event_time_span_listener(_stage_ended)
