"""Mean host wall time of the ``ServeEngine.step`` calls begun in the
window, outside the traced slice where the profiler slows the host,
from the harness's own clock."""


def read(m):
    t = m.facts.get("step_s")
    if not t:
        return None
    return 1e3 * sum(t) / len(t)
