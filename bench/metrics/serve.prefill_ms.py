"""Device time of the admission prefill program in the traced slice,
per request admitted there."""


def read(m):
    n = m.facts.get("admitted_in_slice")
    pf = m.trace["module_matched"].get("prefill") if m.trace else None
    if not n or not pf or not pf["count"]:
        return None
    return pf["ns"] / 1e6 / n
