"""Query compiler: backend compilations that ended inside the window
(jax.monitoring's backend_compile_duration events)."""


def read(rec):
    return len(rec["compiles"])
