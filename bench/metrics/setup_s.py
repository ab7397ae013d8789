"""Process start to the first timed query: data generation and writing,
opening the sources, compilation (or loading it from the cache) and
warm-up, in seconds."""


def read(run):
    return run.setup_s
