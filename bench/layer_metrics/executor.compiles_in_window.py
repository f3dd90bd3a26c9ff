"""Programs compiled or loaded from the compile cache inside the window
(JAX's compile-request events).  Set-up warms every shape, so it reads 0."""


def read(r):
    return r.counters.get("compiles")
