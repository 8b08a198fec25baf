"""Share of the traced serving slice in which no operation ran on the
device."""


def read(m):
    if m.trace is None or not m.trace["window_ns"]:
        return None
    return 100.0 * (1.0 - m.trace["busy_ns"] / m.trace["window_ns"])
