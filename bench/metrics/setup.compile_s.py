"""setup.compile_s: seconds JAX's compile pipeline reported during set-up,
summed over these events (each program traced, lowered, and compiled or
loaded from the persistent cache)."""

EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


def read(run):
    secs = run.setup_events.get("seconds", {})
    return sum(secs.get(e, 0.0) for e in EVENTS)
