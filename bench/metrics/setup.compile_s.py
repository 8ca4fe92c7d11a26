"""Seconds JAX spent producing programs during set-up, persistent-cache
reads included, summed over threads (JAX's ``backend_compile_duration``
events)."""


def read(run):
    return run["setup_compile_s"]
