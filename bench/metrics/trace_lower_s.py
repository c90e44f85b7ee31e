"""trace_lower_s: seconds of Python tracing and lowering to MLIR of the
jitted sweep programs during set-up (JAX's `jaxpr_trace_duration` and
`jaxpr_to_mlir_module_duration` events)."""


def read(ctx):
    c = ctx["setup_compile"]
    return c["trace"] + c["lower"]
