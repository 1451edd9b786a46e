"""compiles_in_window.model: backend compiles (persistent-cache reads
included) that JAX reported inside the window. Set-up warms every shape
the traffic uses, so it should read 0."""


def read(run):
    return run.window_compiles
