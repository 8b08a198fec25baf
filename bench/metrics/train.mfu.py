"""Model FLOP/s utilization of training: model FLOPs per token (6 x the
parameters plus causal attention, nothing recomputed) times the traced
window's tokens per second, over the chips' bf16 peak."""


def read(m):
    f = m.facts
    if not f.get("tokens_per_s"):
        return None
    return (100.0 * f["flops_per_token"] * f["tokens_per_s"]
            / (f["chips"] * m.peaks["bf16_flops"]))
