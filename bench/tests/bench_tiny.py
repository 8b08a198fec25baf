"""Cells of the benchmark cut to a size a CPU test run can hold: the
same files, drivers and limits, with a two-layer model of narrow width
and short sequences."""
import os

from bench import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TINY = dict(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, intermediate_size=128,
            vocab_size=256)


# granite-3-2b's attention widths, so that logits spread as at full
# width (std 0.02 x sqrt(D)), with four layers and a narrower MLP and
# vocabulary that a CPU test run holds
WIDE = dict(TINY, num_hidden_layers=4, hidden_size=2048,
            num_attention_heads=32, num_key_value_heads=8, head_dim=64,
            intermediate_size=2048, vocab_size=2048)


def tiny_cell(name: str, dtype: str = "bfloat16",
              sizes: dict = TINY) -> harness.Cell:
    cell = harness.find_cell(os.path.join(ROOT, "BENCHMARK.json"), name)
    cell.config = dict(cell.config, **sizes, torch_dtype=dtype)
    tr = cell.traffic
    if tr["driver"] == "serve":
        cell.traffic = dict(
            tr, slots=4, window=64, rate=20.0, drain_seconds=20,
            prompt={"median": 12, "sigma": 0.8, "min": 4, "max": 16},
            output={"median": 6, "sigma": 0.5, "min": 2, "max": 8},
            ref_sample={"min_tokens": 40, "max_requests": 8})
    else:
        cell.traffic = dict(tr, seq=32, batch=2, ref_block_rows=2)
    return cell

