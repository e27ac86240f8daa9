"""Run one benchmark cell once on the chip and print its result line.

    python chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json``; its configuration,
traffic mix and per-layer readers are files under ``chipbench/``
(``harness.py`` says how a run goes).  The last line of standard output
is one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``
(the cell's end-to-end metrics, or with ``--trace 1`` its per-layer
ones), ``device`` and, last, ``checks``: every number compared, beside
its limit.  The same checks are the last lines of standard error.

Without a TPU, or with fewer chips than the cell asks for, it exits with
code 2 and prints no result.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness

    try:
        out = harness.run_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace), t_process=T_PROCESS,
                               log=lambda s: print(s, file=sys.stderr))
    except harness.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    for name, c in out["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
