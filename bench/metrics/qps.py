"""Answered queries per second over the whole window, which runs from its
first submit until the last query submitted in it is answered."""
from yardstick.stats import rate


def read(rec):
    return rate(rec["answered"], rec["window_s"])
