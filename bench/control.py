#!/usr/bin/env python3
"""The control: the reference put in the program's place, in bfloat16.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds <s>

For each seed it makes the cell's collection, query pool, ε and window
schedule exactly as a run does, answers every request of the window
from distances computed with both operands rounded to bfloat16 (the
precision below the configuration's float32), and compares those answers
with the float64 reference through the same ``reference.check`` a run
uses.  It prints the numbers per seed and, last, the smallest reading of
each over the seeds: the upper readings the limits in
``reference.LIMITS`` lie below.  The benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".bench_jax_cache")
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

import data  # noqa: E402
import harness  # noqa: E402
import loadgen  # noqa: E402
import reference  # noqa: E402


def control_numbers(cfg: dict, mix: dict, seed: int, seconds: float) -> dict:
    """The compared numbers of the bfloat16 control for one seed."""
    db = data.random_walks(seed, cfg["rows"], cfg["n"])
    pool, _ = data.make_pool(db, seed, int(mix["pool"]), mix["noise_sigma"])
    eps = harness.range_eps(db, pool, int(mix["range_rank"]))
    sched = loadgen.schedule(mix, seed, seconds)
    used = sorted(set(sched.query.tolist()))
    slot = {p: i for i, p in enumerate(used)}
    asks = [(slot[int(p)], "knn" if kn else "range", int(k), float(eps[p]))
            for p, kn, k in zip(sched.query, sched.knn, sched.k)]
    queries = pool[used]
    got = reference.control_answers(db, queries, asks)
    served = [[] for _ in used]
    for (qi, *_), (ids, _) in zip(asks, got):
        served[qi].extend(ids.tolist())
    ref = reference.build_reference(db, queries, served)
    return reference.check(ref, asks, got, cfg["rows"], unanswered=0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    spec = harness.load_json(ROOT / "BENCHMARK.json")
    from run import cell_spec, device_check

    cell, cfg, mix, _ = cell_spec(spec, args.workload)
    _, dev, log = harness.device_info()
    device_check(int(cell["chips"]))(dev)
    lowest = {}
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        nums = control_numbers(cfg, mix, seed, args.seconds)
        log("control", workload=args.workload, seed=seed,
            seconds=time.perf_counter() - t0,
            **{k: v["value"] for k, v in nums.items()},
            fails=not reference.passes(nums))
        for k, v in nums.items():
            lowest[k] = min(lowest.get(k, float("inf")), v["value"])
    print(json.dumps({"workload": args.workload, "device": dev,
                      "control_lowest": lowest,
                      "limits": reference.LIMITS}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
