"""Front end (serve/frontend.py): time each request waited from its
submit to its batch's dispatch (``serve_batch`` spans'
``queue_wait_s``, the sum over the batch's requests: the batcher's
wake-up and coalescing sleep), per answered query."""


def read(rec):
    waits = [s.attrs["queue_wait_s"] for s in rec["spans"]
             if s.name == "serve_batch" and s.attrs.get("queue_wait_s") is not None]
    if not waits or not rec["answered"]:
        return None
    return 1e3 * sum(waits) / rec["answered"]
