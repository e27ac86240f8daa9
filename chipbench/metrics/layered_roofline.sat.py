"""The layered (Time-DS-FD) fleet update's share of its roofline: the
least time the chip could take for a traced tick over the update
program's device time per tick.

Every row goes to every one of the ``levels = ceil(log2(eps N R)) + 1``
layers, each a DS-FD state of ``cost.state_bytes``, so ``cost.py``'s
terms are taken ``levels`` times, with ``R`` from the configuration:

* bytes: ``2 * levels * state_bytes * streams + rows * d * 4``;
* flops: ``arrivals * levels * 2 * d * ell``, the arrivals being the
  tick's rows times the configuration's ``arrival_p`` (idle units are
  zero rows, which need no projection).

Both are lower bounds, so the share cannot pass 100%."""

import math

import _lib
import cost


def levels(system: dict) -> int:
    x = float(system["eps"]) * int(system["window"]) * float(system["R"])
    return max(math.ceil(math.log2(max(x, 2.0))), 1) + 1


def least_seconds(run, rows: float) -> float:
    sy, peaks = run.system, run.peaks
    d, L = int(sy["d"]), levels(sy)
    arrivals = rows * float(run.cfg["rows"]["arrival_p"])
    flops = arrivals * L * 2 * d * cost.ell(sy)
    nbytes = 2 * L * cost.state_bytes(sy) * run.S + rows * d * 4
    return max(flops / float(peaks["bf16_flops_per_s"]),
               nbytes / float(peaks["hbm_bytes_per_s"]))


def read(run):
    s = _lib.update_device_s(run)
    ticks = _lib.trace_ticks(run)
    if (s is None or not ticks or run.peaks is None
            or "R" not in run.system):
        return None
    rows = sum(t["rows"] for t in ticks) / len(ticks)
    return 100.0 * least_seconds(run, rows) / s
