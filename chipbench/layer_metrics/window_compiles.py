"""Programs compiled or loaded from the compile cache inside the window
(JAX's backend-compile event, which fires for both)."""


def read(r):
    return r.compiles
