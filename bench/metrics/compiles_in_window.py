"""Programs compiled or loaded from the persistent cache during the
measured serve (JAX monitoring events); 0 when the warm-up covered every
shape the window uses."""


def read(run):
    return run.compiles
