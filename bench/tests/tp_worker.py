"""Runs the small cell at tensor-parallel degree 4 on four virtual CPU
devices, the Pallas kernels in interpret mode under their shard_map
boundary; with ``no_psum``, the exchange between chips is left out (each
chip keeps its own partial sums).  Prints one JSON line.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python bench/tests/tp_worker.py [clean|no_psum]
"""
import dataclasses
import json
import os
import sys
import time

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), os.path.join(os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))), "src")]

import jax  # noqa: E402

from bench.run import run_cell  # noqa: E402
from bench.tests.tiny import tiny_cell  # noqa: E402


def main() -> None:
    cell = tiny_cell()
    conf = dict(cell.config, num_attention_heads=8, num_key_value_heads=4)
    conf["serving"] = dict(conf["serving"], chips=4, tp=4)
    cell = dataclasses.replace(cell, config=conf, chips=4)
    if sys.argv[1] == "no_psum":
        jax.lax.psum = lambda x, axis_name, **kw: x
    res = run_cell(cell, 0, 2.0, False, require_tpu=False,
                   backend="pallas_interpret", t_start=time.perf_counter())
    print(json.dumps({"correct": res["correct"], "devices":
                      res["device"]["count"], "checks": res["checks"]}))


if __name__ == "__main__":
    main()
