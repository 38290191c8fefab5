"""Storage engine (storage/tilestore.py TileStore.member_stats): host
time computing the tile statistics of each new member subset
(``member_stats`` spans, opened on a cache miss), per answered query."""
from yardstick.spans import named


def read(rec):
    spans = named(rec, "member_stats")
    if not spans or not rec["answered"]:
        return None
    return 1e3 * sum(s.wall_s for s in spans) / rec["answered"]
