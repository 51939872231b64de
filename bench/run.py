#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  ``BENCHMARK.json`` names the cell; the
cell names its configuration and traffic mix, each a file under
``bench/``.  With ``--trace 0`` the result carries the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics, read from a profiler
trace of the same window.  The last line of standard output is the
result object; the numbers compared with the float64 reference are the
last lines of standard error.  Without a TPU, or with fewer chips than
the cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
# JAX's persistent compilation cache lives at a fixed path inside the
# checkout, so that only a cell's first run there compiles; set before
# JAX is imported, and taken as given by the program's own cache hook.
# It is the benchmark's own directory: nothing else writes there.
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".bench_jax_cache")
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

import harness  # noqa: E402


class NoChip(SystemExit):
    pass


def cell_spec(spec: dict, workload: str):
    """(cell, configuration entry, traffic mix, metric entries by mode)."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cfg = harness.load_json(ROOT / conf["file"])
    mix = harness.load_json(BENCH / "traffic" / f"{cell['traffic']}.json")

    def applies(m):
        return workload in m.get("workloads", [workload])

    return cell, cfg, mix, {
        0: [m for m in spec["end_to_end"] if applies(m)],
        1: [m for m in spec["per_layer"] if applies(m)]}


def device_check(chips: int):
    def check(dev: dict) -> dict:
        if dev["platform"] != "tpu":
            print(f"bench: no TPU (platform={dev['platform']!r}); the "
                  "benchmark runs on the chip only", file=sys.stderr)
            raise NoChip(3)
        if dev["count"] < chips:
            print(f"bench: the cell needs {chips} chips, JAX sees "
                  f"{dev['count']}", file=sys.stderr)
            raise NoChip(3)
        peaks = harness.load_json(BENCH / "peaks.json")["chips"]
        if dev["kind"] not in peaks:
            print(f"bench: no peaks for device kind {dev['kind']!r} in "
                  "bench/peaks.json", file=sys.stderr)
            raise NoChip(4)
        return peaks[dev["kind"]]
    return check


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default="",
                    help="directory to write the trace's events and a "
                         "description of its planes into")
    args = ap.parse_args(argv)
    spec = harness.load_json(ROOT / "BENCHMARK.json")
    cell, cfg, mix, metrics = cell_spec(spec, args.workload)
    result = harness.run(
        cell, cfg, mix, args.seed, args.seconds, bool(args.trace),
        metrics[args.trace], device_check=device_check(int(cell["chips"])),
        keep_trace=args.keep_trace or None)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
