"""Device time per training step under the gradient sync's ops (the
rounds' transfers, the bucket combine, an all-reduce) that no other op
overlaps, from the trace of the window, averaged over the chips."""


def read(m):
    sync = m.trace.get("sync") if m.trace else None
    steps = m.facts.get("steps")
    if not sync or not steps or not any(
            o["count"] for o in sync["ops"].values()):
        return None
    return sync["exposed_ns"] / 1e6 / steps
