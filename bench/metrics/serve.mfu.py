"""The decode step's share of its roofline, over the engine steps begun
in the window outside the traced slice: for each that decoded, the
least time the chip needs for the step's work (the larger of its FLOPs
at the bf16 peak and its bytes at HBM bandwidth: the weights once, the
keys and values of the live positions of the live slots once), summed,
over the host-clock time of those engine steps."""
from bench import flops


def read(m):
    steps = m.facts.get("decode_steps")
    if not steps:
        return None
    least = sum(flops.least_time(**_work(m.facts["config"], s, p),
                                 peaks=m.peaks)["s"]
                for s, p, _ in steps)
    return 100.0 * least / sum(t for _, _, t in steps)


def _work(cfg, slots, positions):
    w = flops.decode_step(cfg, slots, positions)
    return {"flops": w["flops"], "nbytes": w["bytes"]}
