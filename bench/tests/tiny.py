"""A cell at a size a CPU test can run: the phi4-mini file's structure at
smoke widths, and the chat mix scaled down."""
import json
import os
import time

from bench import registry
from bench.run import run_cell

# Widest logit gap allowed at this size (the chip cells' limits live in
# their configuration files).  Clean runs here read 0 to 0.024 on seeds
# 0-3, the float8 control 0.098 to 0.43, the planted faults 1.8 and more.
TINY_LIMIT = 0.06


def tiny_cell(limit=TINY_LIMIT, vocab=2048) -> registry.Cell:
    with open(os.path.join(registry.BENCH_DIR, "configs",
                           "phi4-mini.json")) as f:
        conf = json.load(f)
    conf.update(hidden_size=128, intermediate_size=256, num_hidden_layers=2,
                num_attention_heads=4, num_key_value_heads=2,
                vocab_size=vocab, logit_gap_limit=limit)
    conf["serving"] = dict(conf["serving"], max_batch=4, buckets=2)
    mix = dict(registry.traffic("chat"), rate_per_s=4.0, fill_seconds=1.0)
    mix["prompt_tokens"] = dict(mix["prompt_tokens"], median=24, min=8,
                                max=64)
    mix["output_tokens"] = dict(mix["output_tokens"], median=8, min=2,
                                max=24)
    ends = [{"name": n, "unit": u} for n, u in (
        ("ttft_p50_ms", "ms"), ("itl_p50_ms", "ms"), ("itl_p98_ms", "ms"),
        ("setup_s", "s"))]
    return registry.Cell("tiny.chat", "tiny", conf, "chat", mix, 1, ends, [])


def run_tiny(seed, seconds=2.0, **kw):
    return run_cell(tiny_cell(), seed, seconds, False, require_tpu=False,
                    backend="reference", t_start=time.perf_counter(), **kw)
