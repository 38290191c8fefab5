"""Executors (query/index.py, query/executors.py): host time gathering
members and enqueueing device work (``dispatch`` spans) less circuit
building inside them (``compile`` children), per answered query."""
from yardstick.spans import own_ms_per_query


def read(rec):
    return own_ms_per_query(rec, "dispatch", less="compile")
