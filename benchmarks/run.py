# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark driver: runs every paper-table benchmark plus the beyond-paper
ablations.  ``python -m benchmarks.run [--only table1,...] [--json PATH]
[--smoke]``.

``--json`` additionally parses every ``name,value,derived`` CSV line the
suites emit into a ``BENCH_*.json`` trajectory file (see EXPERIMENTS.md
§Trajectories): one JSON object per run, so successive PRs accumulate a
machine-readable perf history.

``--smoke`` selects the CI tier (``REPRO_BENCH_SMOKE=1``): the same
database and query workload over a trimmed parameter grid, so each
emitted record matches the name — and for deterministic op-count metrics
the value — of its full-tier counterpart.  The bench-regression gate
(``scripts/bench_gate.py``) runs every suite this way and diffs the
records against the committed baselines.
"""
from __future__ import annotations

import argparse
import io
import json
import os
import re
import sys
import time

SUITES = ("table1", "figure2", "tightness", "pruning", "repr", "engine",
          "knn", "index_io", "serve", "subseq", "quantized", "obs",
          "chaos", "dist_quantized")

_CSV_LINE = re.compile(r"^([a-z0-9_][a-z0-9_/.+-]*),(-?[0-9.eE+]+),(.*)$")


class _Tee(io.TextIOBase):
    """stdout passthrough that collects the suites' CSV record lines."""

    def __init__(self, wrapped):
        self.wrapped = wrapped
        self.records = []
        self._buf = ""

    def write(self, s):
        self.wrapped.write(s)
        self._buf += s
        while "\n" in self._buf:
            line, self._buf = self._buf.split("\n", 1)
            m = _CSV_LINE.match(line.strip())
            if m:
                self.records.append({
                    "name": m.group(1),
                    "us_per_call": float(m.group(2)),
                    "derived": m.group(3),
                })
        return len(s)

    def flush(self):
        self.wrapped.flush()


def main() -> None:
    from repro.runtime.compile_cache import use_compile_cache

    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=",".join(SUITES),
                    help="comma-separated subset of: " + ",".join(SUITES))
    ap.add_argument("--json", default="",
                    help="also write the parsed records to this "
                         "BENCH_*.json trajectory file")
    ap.add_argument("--smoke", action="store_true",
                    help="CI tier: trimmed parameter grid, same record "
                         "names/values on the overlapping cells")
    args = ap.parse_args()
    chosen = [s.strip() for s in args.only.split(",") if s.strip()]

    if args.smoke:
        # Must land before the suite modules import benchmarks.common.
        os.environ["REPRO_BENCH_SMOKE"] = "1"

    from . import (chaos_recovery, dist_quantized, engine_throughput,
                   figure2_curves, index_io, knn_latency, obs_overhead,
                   pruning_power, quantized_memory, representations,
                   serve_load, subseq_latency, table1_latency, tightness)
    mains = {"table1": table1_latency.main, "figure2": figure2_curves.main,
             "tightness": tightness.main, "pruning": pruning_power.main,
             "repr": representations.main,
             "engine": engine_throughput.main, "knn": knn_latency.main,
             "index_io": index_io.main, "serve": serve_load.main,
             "subseq": subseq_latency.main,
             "quantized": quantized_memory.main,
             "obs": obs_overhead.main,
             "chaos": chaos_recovery.main,
             "dist_quantized": dist_quantized.main}
    for name in chosen:
        if name not in mains:
            print(f"unknown suite {name!r}", file=sys.stderr)
            sys.exit(2)

    tee = _Tee(sys.stdout) if args.json else None
    if tee is not None:
        sys.stdout = tee
    try:
        for name in chosen:
            print(f"\n===== {name} =====")
            t0 = time.perf_counter()
            mains[name]()
            print(f"# {name} done in {time.perf_counter() - t0:.1f}s")
    finally:
        if tee is not None:
            sys.stdout = tee.wrapped
    if tee is not None:
        with open(args.json, "w") as f:
            json.dump({"suites": chosen, "records": tee.records}, f, indent=1)
        print(f"# wrote {len(tee.records)} records to {args.json}")


if __name__ == "__main__":
    main()
