"""setup_s: process start (the top of run.py) to the first request of the
window: JAX and chip start-up, weights, molecule pool, ladder warm-up."""


def read(run):
    return run.setup_s
