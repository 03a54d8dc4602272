"""Seconds JAX spent in backend compiles (persistent-cache reads
included) during set-up: jax.monitoring."""


def read(run):
    return run.get("setup_compile_s")
