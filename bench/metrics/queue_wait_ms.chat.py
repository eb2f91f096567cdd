"""Mean time a request due in the window waited for a slot: the
scheduler's admission stamp (``Request.admit_t``) minus the due time, over
every request due in the window that was admitted."""


def read(ctx):
    waits = ctx.get("queue_waits") if ctx.get("kind") == "serve" else None
    if not waits:
        return None
    return 1e3 * sum(waits) / len(waits)
