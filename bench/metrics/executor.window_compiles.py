"""Programs compiled or read from the compile cache inside the measured
window (JAX's ``backend_compile_duration`` events). Set-up warms every
shape the window uses, so this reads 0."""


def read(run):
    return run["window_compiles"]
