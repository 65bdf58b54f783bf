"""Programs compiled during set-up: JAX's program requests less the
persistent compile cache's hits. 0 when every program loads from the
cache."""


def read(run):
    return run.setup_compiles
