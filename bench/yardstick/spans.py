"""Host time read from the program's span trees (``repro.obs.trace``):
the root spans that completed in the traced window, as the harness
collects them."""
from __future__ import annotations


def named(rec: dict, name: str) -> list:
    """Every span called ``name`` in the window's span trees."""
    return [s for root in rec["spans"] for s in root.iter() if s.name == name]


def own_ms_per_query(rec: dict, name: str, less: str):
    """Wall time of the ``name`` spans less that of their direct ``less``
    children, in ms per answered query; None without such spans."""
    spans = named(rec, name)
    if not spans or not rec["answered"]:
        return None
    own = sum(s.wall_s - sum(c.wall_s for c in s.children if c.name == less)
              for s in spans)
    return 1e3 * own / rec["answered"]
