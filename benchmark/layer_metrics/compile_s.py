"""Seconds JAX spent building or fetching programs up to the end of
set-up (cdt_jax_compile_time_seconds)."""


def read(material):
    return float(material["after_setup"]["compile_s"])
