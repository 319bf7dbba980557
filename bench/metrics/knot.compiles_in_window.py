"""Backend compiles and persistent-cache loads inside the window (JAX
monitoring events)."""


def read(r):
    return r.get("compiles_in_window")
