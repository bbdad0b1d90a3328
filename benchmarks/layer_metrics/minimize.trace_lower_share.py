"""minimize.trace_lower_share (%): seconds of jaxpr tracing and MLIR lowering inside the window (jax.monitoring; no cache keeps them) over the window's seconds."""

from lib.readers import trace_lower_share


def read(obs):
    return trace_lower_share(obs)
