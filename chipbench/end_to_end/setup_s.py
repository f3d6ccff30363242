"""Process start to the first measured request: JAX and TPU start-up,
compile-cache loads, data generation, preload and warm-up."""


def read(run):
    return run.setup_s
