"""JAX backend compilations (or persistent-cache loads) inside the measured
window, heard on JAX's compile-duration monitoring event: every shape should
have been warmed up, so this should be 0."""


def read(ctx):
    return ctx["compiles_in_window"]
