"""§Perf hillclimb driver: re-run the three chosen cells with candidate
optimizations and diff the roofline terms against the baseline artifacts.

    PYTHONPATH=src python -m benchmarks.hillclimb          # run variants
    PYTHONPATH=src python -m benchmarks.hillclimb --report # table only

Cells (per the brief — baseline table, EXPERIMENTS.md §Roofline):
    mixtral-8x22b     x train_4k   (MOST COLLECTIVE-BOUND: 240 s collective)
    qwen3-moe-30b-a3b x decode_32k (WORST ROOFLINE FRACTION: useful 0.020)
    internlm2-20b     x train_4k   (MOST REPRESENTATIVE of the technique:
                                    pure selector-driven dense GEMM stack)

Variants are cumulative where the tag chains flags.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import subprocess
import sys

BASE = os.path.join(os.path.dirname(__file__), "..")
OUT = os.path.join(BASE, "experiments", "hillclimb")

# (cell, variant-tag, extra dryrun flags)
RUNS = [
    ("internlm2-20b", "train_4k", "kvrep", ["--kv-repeat-weights"]),
    ("internlm2-20b", "train_4k", "kvrep_mb", ["--kv-repeat-weights",
                                               "--microbatches", "0"]),
    ("internlm2-20b", "train_4k", "kvrep_mb_sp", ["--kv-repeat-weights",
                                                  "--microbatches", "0",
                                                  "--sp-stash"]),
    ("mixtral-8x22b", "train_4k", "kvrep", ["--kv-repeat-weights"]),
    ("mixtral-8x22b", "train_4k", "kvrep_mb", ["--kv-repeat-weights",
                                               "--microbatches", "0"]),
    ("qwen3-moe-30b-a3b", "decode_32k", "moedense", ["--moe-dense-decode"]),
    ("qwen3-moe-30b-a3b", "decode_32k", "moedense_kvrep",
     ["--moe-dense-decode", "--kv-repeat-weights"]),
    # Attribution runs for the bf16-TP-reduction change (kernels/ref.py):
    # no flags => isolates the pure bf16-collective effect vs baseline.
    ("internlm2-20b", "train_4k", "bf16coll", []),
    ("internlm2-20b", "train_4k", "bf16coll_kvrep", ["--kv-repeat-weights"]),
    ("mixtral-8x22b", "train_4k", "bf16coll", []),
    ("qwen3-moe-30b-a3b", "decode_32k", "bf16coll", []),
]


def run_variants(only=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(BASE, "src")
    for arch, shape, tag, flags in RUNS:
        if only and tag != only:
            continue
        out_dir = os.path.join(OUT, tag)
        print(f"== {arch} x {shape} [{tag}] {' '.join(flags)}")
        cmd = [sys.executable, "-u", "-m", "repro.launch.dryrun",
               "--arch", arch, "--shape", shape, "--with-probes",
               "--out", out_dir, *flags]
        r = subprocess.run(cmd, env=env, cwd=BASE)
        if r.returncode:
            print(f"   FAILED rc={r.returncode}")


def _load(path):
    out = {}
    for f in glob.glob(os.path.join(path, "*.json")):
        r = json.load(open(f))
        out[(r["arch"], r["shape"])] = r
    return out


def report():
    base = _load(os.path.join(BASE, "experiments", "dryrun"))
    print(f"{'cell':34s} {'variant':14s} {'compute_s':>10s} {'memory_s':>9s} "
          f"{'coll_s':>9s} {'roofline_s':>10s} {'bound':>10s} {'useful':>7s}")

    def row(r, tag):
        rf = r["roofline"]
        cell = f"{r['arch']} x {r['shape']}"
        print(f"{cell:34s} {tag:14s} {rf['compute_s']:10.3f} "
              f"{rf['memory_s']:9.3f} {rf['collective_s']:9.3f} "
              f"{rf['roofline_s']:10.3f} {rf['bottleneck']:>10s} "
              f"{rf['useful_flop_ratio']:7.3f}")

    cells = sorted({(a, s) for a, s, _, _ in RUNS})
    for (arch, shape) in cells:
        if (arch, shape) in base:
            row(base[(arch, shape)], "baseline")
        for tag in [t for a, s, t, _ in RUNS if (a, s) == (arch, shape)]:
            v = _load(os.path.join(OUT, tag))
            if (arch, shape) in v:
                row(v[(arch, shape)], tag)
        print()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--report", action="store_true")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    if not args.report:
        run_variants(only=args.only)
    report()


if __name__ == "__main__":
    main()
