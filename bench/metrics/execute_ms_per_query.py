"""Executors (query/index.py execute_many): host time to plan, find or
build circuits and enqueue device work (``execute_many`` spans), per
answered query."""


def read(rec):
    spans = [s for root in rec["spans"] for s in root.iter()
             if s.name == "execute_many"]
    if not spans or not rec["answered"]:
        return None
    return 1e3 * sum(s.wall_s for s in spans) / rec["answered"]
