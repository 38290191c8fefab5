"""Planner (query/index.py explain): host time planning each query
(``plan`` spans) less the member statistics computed inside them
(``member_stats`` children, read by member_stats_ms_per_query), per
answered query."""
from yardstick.spans import own_ms_per_query


def read(rec):
    return own_ms_per_query(rec, "plan", less="member_stats")
