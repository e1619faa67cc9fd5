"""Tiny versions of the benchmark's cells, for running the harness on the
CPU in tests. Same files, same code paths; only the sizes shrink."""
from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402

TINY_MODEL = {
    # a CPU multiplies float32 at full precision, not in one bfloat16 pass
    # as a TPU does at XLA's default: the reference follows the platform
    "elasticbert12": {"num_hidden_layers": 3, "hidden_size": 64,
                      "num_attention_heads": 4, "intermediate_size": 128,
                      "vocab_size": 600,
                      "reference_numerics": {"mm": "f32", "act": "f32"}},
    "qwen3-1.7b": {"num_hidden_layers": 3, "hidden_size": 256,
                   "num_attention_heads": 4, "num_key_value_heads": 2,
                   "head_dim": 64, "intermediate_size": 512,
                   "vocab_size": 512},
}
TINY_TRAFFIC = {
    "open_loop": {"seq_len": 16, "serving": {"batch_size": 8},
                  "arrivals": {"rate_per_s": 150.0}, "compare": {"samples": 64}},
    "offline": {"seq_len": 16, "pool_rows": 256,
                "serving": {"batch_size": 8}, "compare": {"samples": 64}},
    "decode_closed": {"batch_size": 2, "prompt_len": 8, "new_tokens": 6,
                      "alpha": {"probe_steps": 4}, "compare": {"sequences": 32}},
}


def merge(base, over):
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(base[k], v) if isinstance(v, dict) and isinstance(
            base.get(k), dict) else v
    return out


def tiny_cell(name: str) -> "harness.Cell":
    """The named cell at a size the CPU runs in seconds."""
    cell = harness.load_cell(name)
    cell.spec = merge(cell.spec, TINY_MODEL[cell.config_name])
    cell.traffic = merge(cell.traffic, TINY_TRAFFIC[cell.traffic["kind"]])
    return cell


def run_tiny(name: str, *, seconds: float = 0.5, seed: int = 3,
             trace: bool = False, control: bool = False,
             runtime_hook=None):
    import time
    import jax
    cell = tiny_cell(name)
    return harness.run_cell(cell, seed=seed, seconds=seconds, trace=trace,
                            t_start=time.perf_counter(), control=control,
                            runtime_hook=runtime_hook,
                            devices=jax.devices()[:1])
