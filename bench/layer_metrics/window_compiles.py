"""Compile: backend compiles (persistent-cache loads included) inside the
window, from a jax.monitoring listener.  0 means set-up warmed every
shape the traffic reached."""


def read(run):
    return run.compiles
