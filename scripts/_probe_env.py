"""Shared probe environment setup (import after the repo-root sys.path
insert, call BEFORE any jax op): the persistent compilation cache every
probe, the bench and the daemon share.  The platform is JAX_PLATFORMS'
business alone."""

from gubernator_tpu.config import place_compile_cache


def setup():
    place_compile_cache()
