"""Front end (serve/frontend.py): window time outside the server's batch
execution (``serve_batch`` spans), per answered query -- submit
analysis, the coalescing sleep and fan-out."""


def read(rec):
    batches = [s for s in rec["spans"] if s.name == "serve_batch"]
    if not batches or not rec["answered"]:
        return None
    inside = sum(s.wall_s for s in batches)
    return 1e3 * (rec["window_s"] - inside) / rec["answered"]
