"""Host spans of the APSP front door, on the profiler's clock.

A span is a ``jax.profiler.TraceAnnotation``: with no profiler trace
running it records nothing and costs about a microsecond; under a trace it
lands beside the device's operations, its integer counts kept as event
stats.  Spans nest on the calling thread, which is all the parentage the
synchronous front doors need.  Counts known only at the end of a span are
added with ``set_metadata`` on the object ``span`` returns.
"""

import jax

SOLVE = "apsp.solve"              # one solve call; counts n, with_pred
SOLVE_BATCH = "apsp.solve_batch"  # one solve_batch call; graphs, buckets, with_pred
VALIDATE = "apsp.validate"        # NaN scan of the input; bytes_to_host, on_device
DISPATCH = "apsp.dispatch"        # cast and solver enqueue; pred_bytes
NEGCYCLE = "apsp.negcycle"        # solved-diagonal check; bytes_to_host, on_device
PACK = "apsp.pack"                # one bucket's padded stack; work and bytes
FETCH = "apsp.fetch"              # inputs read as host arrays
SCATTER = "apsp.scatter"          # the batch frame gathered on the device


def span(name: str, **counts: int) -> jax.profiler.TraceAnnotation:
    """A host span ``name`` carrying ``counts`` (use as a context manager)."""
    return jax.profiler.TraceAnnotation(name, **counts)
