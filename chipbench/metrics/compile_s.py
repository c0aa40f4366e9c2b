"""Seconds JAX spent tracing, lowering and compiling (or loading from
the persistent cache) during set-up, summed from its own monitoring
events."""

EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
          "/jax/core/compile/jaxpr_to_mlir_module_duration",
          "/jax/core/compile/backend_compile_duration")


def read(run):
    secs = [s for e, s in run.setup_events if e in EVENTS]
    return float(sum(secs)) if secs else None
