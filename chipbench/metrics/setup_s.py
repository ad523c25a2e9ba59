"""Process start to the window's opening: imports, weights, engine,
warm-up (compilation, or loading it from the cache) and any fill."""


def read(run):
    return run.setup_s
