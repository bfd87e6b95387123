"""Programs traced and lowered inside the window, whether then compiled or
fetched from the persistent cache: JAX's `jaxpr_to_mlir_module` events."""


def read(r):
    return float(r.lowered)
