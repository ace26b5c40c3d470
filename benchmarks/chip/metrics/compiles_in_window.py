"""Programs compiled or loaded from the compile cache while the window
ran (JAX's backend-compile events); 0 when set-up warmed every shape."""


def read(run):
    return float(run.compiles_in_window)
