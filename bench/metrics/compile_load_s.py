"""compile_load_s: seconds of XLA compilation, or of loading the program
from the persistent cache, during set-up (JAX's `backend_compile_duration`
events, which wrap both)."""


def read(ctx):
    return ctx["setup_compile"]["compile"]
