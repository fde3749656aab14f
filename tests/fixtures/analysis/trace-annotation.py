# Golden negative case for check id ``trace-annotation``: one function
# uses jax.profiler.TraceAnnotation directly, another calls the gate's
# trace_annotation itself — a device annotation with no span behind it.
# Only SpanTracer.span opens annotations (through its annotate hook).
import jax

from active_learning_tpu.telemetry import profiler


def annotate(name):
    return jax.profiler.TraceAnnotation(name)


def scored(name, fn):
    with profiler.trace_annotation(name):
        return fn()
