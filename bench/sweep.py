#!/usr/bin/env python3
"""Find a cell's knee on the chip: the highest offered rate it sustains.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 5,10,20,40

One process sets the cell up once, then drives one open-loop window of
the cell's own mix at each rate, lowest first, and prints per rate the
completed rate, p50 and p95 (due time to answer) and how long after the
window's close the last answer came.  A rate is sustained when every
request is answered, the last answer comes within a second of the
close (the backlog does not grow) and at least 90% complete inside the
window.  It
stops at the first rate that is not.  The knee is the highest rate
sustained; a cell runs at a fixed rate near four fifths of it, written
into its traffic file.  The sweep is a tool, not a cell: its numbers
are not compared.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".bench_jax_cache")
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

import numpy as np  # noqa: E402

import harness  # noqa: E402
from run import cell_spec, device_check  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, queries/s")
    args = ap.parse_args(argv)
    spec = harness.load_json(ROOT / "BENCHMARK.json")
    cell, cfg, mix, _ = cell_spec(spec, args.workload)
    import jax

    from repro.runtime.compile_cache import use_compile_cache

    use_compile_cache()
    harness.cache_everything()
    _, dev, log = harness.device_info()
    device_check(int(cell["chips"]))(dev)
    sess = harness.Session(cfg, mix, args.seed, False, log)
    rows, knee = [], None
    for rate in sorted(float(r) for r in args.rates.split(",")):
        m = dict(mix, rate_qps=rate)
        _, sent, t_open, t_close, batches = sess.window(m, args.seconds)
        ok = harness.window_line(log, sess, sent, t_close, batches)
        _, done = sess.times(sent)
        lat = np.where(ok, done - sent.due, np.inf)
        qps = float(np.sum(ok & (done <= t_close))) / args.seconds
        lag = sess.t_last - t_close
        held = bool(ok.all()) and lag <= 1.0 and qps >= 0.9 * rate
        row = {"rate_qps": rate, "qps": qps,
               "p50_ms": harness.quantile(lat, 0.5) * 1e3,
               "p95_ms": harness.quantile(lat, 0.95) * 1e3,
               "last_answer_after_close_s": lag,
               "refused_or_failed": int((~ok).sum()), "sustained": held}
        rows.append(row)
        log("sweep", **row)
        if not held:
            break
        knee = rate
    sess.close()
    print(json.dumps({"workload": args.workload, "device": dev,
                      "knee_qps": knee, "rates": rows}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
