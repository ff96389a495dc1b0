"""Programs compiled inside the window (JAX's backend-compile events)."""


def read(run):
    return None if run.stream("chains") is None else run.compiles
