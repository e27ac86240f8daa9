"""Error-vs-space sweeps — reproduces Figures 4, 5, 6 (sequence-based) and
Figures 8, 9 (time-based) plus the empirical side of Table 1.

For each algorithm we sweep the precision parameter (1/ε) and record the
*maximum sketch rows* ever held against the average / maximum relative
covariance error over all queries — exactly the trade-off the paper plots.
"""

from __future__ import annotations

import argparse
import math
import traceback
from typing import Dict, List

import numpy as np

from benchmarks.common import WindowOracle, eval_queries, run_sketch, \
    write_csv
from repro.data.streams import get_stream
from repro.sketch.api import available_sketches


class SweepError(RuntimeError):
    """Some cells of a sweep failed; ``rows`` holds the cells that ran."""

    def __init__(self, failures: List[str], rows: List[Dict]):
        super().__init__(f"{len(failures)} sweep cell(s) failed: "
                         + "; ".join(failures))
        self.failures, self.rows = failures, rows


def sweep(dataset: str, *, scale: float = 0.1, seed: int = 0,
          eps_list=(1 / 4, 1 / 8, 1 / 16, 1 / 32),
          algs=("dsfd", "lmfd", "difd", "swr", "swor"),
          queries: int = 24) -> List[Dict]:
    spec = get_stream(dataset, scale=scale, seed=seed)
    rows, N, ts = spec.rows, spec.window, spec.timestamps
    time_based = ts is not None
    R = spec.R
    n = rows.shape[0]
    q = max(N // 4, n // queries)
    oracle = WindowOracle(rows, N, ts)
    min_t = N  # evaluate only full windows
    out, failures = [], []
    for eps in eps_list:
        for alg in algs:
            try:
                # every variant streams through the same registry entry point
                name, hyper = alg, {}
                if alg == "dsfd":
                    if time_based:
                        name, hyper = "time-dsfd", {"R": R}
                    elif R > 1.001:
                        name, hyper = "seq-dsfd", {"R": R}
                elif alg == "difd":
                    if time_based:
                        continue        # DI-FD is sequence-based only (§2.2)
                    hyper = {"R": R}
                elif alg in ("seq-dsfd", "time-dsfd"):
                    hyper = {"R": R}
                elif alg in ("swr", "swor"):
                    hyper = {"seed": seed}
                if name not in available_sketches():
                    continue
                qs, peak, wall = run_sketch(name, rows, eps=eps, window=N,
                                            query_every=q, timestamps=ts,
                                            **hyper)
                avg, worst = eval_queries(oracle, qs, min_t=min_t)
                out.append({
                    "dataset": spec.name, "alg": alg, "inv_eps": round(1 / eps),
                    "max_rows": peak, "avg_err": avg, "max_err": worst,
                    "wall_s": round(wall, 3), "n": n, "window": N,
                    "R": round(R, 2),
                })
                print(f"  {spec.name:<10s} {alg:<5s} 1/eps={1/eps:4.0f} "
                      f"rows={peak:6d} avg={avg:.5f} max={worst:.5f} "
                      f"({wall:.1f}s)", flush=True)
            except Exception as e:   # noqa: BLE001 — finish the sweep,
                # then fail it: every cell's traceback is printed here
                failures.append(f"{dataset} {alg} eps={eps}: {e!r}")
                print(f"  {failures[-1]}: FAILED", flush=True)
                traceback.print_exc()
    if failures:
        raise SweepError(failures, out)
    return out


def main(argv=None) -> List[Dict]:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="synthetic")
    ap.add_argument("--scale", type=float, default=0.1)
    ap.add_argument("--eps", type=float, nargs="*", default=None)
    ap.add_argument("--algs", nargs="*", default=None)
    args = ap.parse_args(argv)
    kw = {}
    if args.eps:
        kw["eps_list"] = args.eps
    if args.algs:
        kw["algs"] = args.algs
    try:
        rows = sweep(args.dataset, scale=args.scale, **kw)
    except SweepError as e:
        print("wrote", write_csv(f"error_space_{args.dataset}.csv", e.rows))
        raise
    print("wrote", write_csv(f"error_space_{args.dataset}.csv", rows))
    return rows


if __name__ == "__main__":
    main()
