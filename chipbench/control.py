"""Readings of a cell's compared numbers: the program's and its control's.

    python chipbench/control.py --workload <name> --seeds 1,2,3 --seconds 30

Runs the cell once per seed, in one process, exactly as ``run.py`` does,
and reads every compared number twice from the same run: once for the
program's answers and once for the configuration's control (its plain
reference with one stated guarantee broken, put in the program's place;
see ``configs/<config>.py``).  Prints one JSON line per seed and a last
line with, for each number, the largest program reading, the smallest
control reading and their ratio, from which a limit is set.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import harness

    program, control = {}, {}
    t0 = T_PROCESS
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            out = harness.run_cell(args.workload, seed, args.seconds, False,
                                   t_process=t0, control=True,
                                   log=lambda s: print(s, file=sys.stderr))
        except harness.NoChip as e:
            print(f"chipbench: {e}", file=sys.stderr)
            return 2
        t0 = time.perf_counter()
        rec = {"seed": seed, "correct": out["correct"],
               "program": {k: c["value"] for k, c in out["checks"].items()},
               "control": out["control"]}
        print(json.dumps(rec), flush=True)
        for k, v in out["control"].items():
            program[k] = max(program.get(k, 0.0), out["checks"][k]["value"])
            control[k] = min(control.get(k, float("inf")), v)
    print(json.dumps({k: {"program_max": program[k],
                          "control_min": control[k],
                          "ratio": control[k] / max(program[k], 1e-300)}
                      for k in program}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
