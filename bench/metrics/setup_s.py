"""Process start to the window's first submit: JAX start-up, data made
from the seed, the index build and the warm-up."""


def read(rec):
    return rec["setup_s"]
