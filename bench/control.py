"""Readings for a cell's limits on ``correct``. For each seed, one run of
the cell (its own window, load and sample of requests) gives the program's
gap numbers; then the control — the reference computed with float8 matmul
operands, the precision step below the configuration's bfloat16 — is put in
the program's place on the same requests, and its gaps go through the same
judgement (``harness.judge``) as the program's. ``--fault`` plants one of
``bench/tests/faults.py`` in the program for every seed. All seeds run in
one process, so set-up and compilation are paid once.

    python3 bench/control.py --workload <name> --seconds <s> --seeds 1 2 3
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def numbers(gaps) -> dict:
    """Every gap number the harness knows, whether or not the cell limits it."""
    import numpy as np

    from bench import harness

    g = np.concatenate(gaps)
    return {k: float(f(g)) for k, f in harness.GAP_NUMBERS.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=None,
                    help="run the control on the first N seeds only")
    ap.add_argument("--fault", default=None,
                    help="a fault of bench/tests/faults.py to plant")
    args = ap.parse_args(argv)

    from bench import harness, reference

    cell = harness.load_cell(args.workload)
    devices = harness.check_devices(cell.chips)
    harness.enable_compile_cache()
    if args.fault:
        import pytest

        from bench.tests import faults
        getattr(faults, args.fault)(pytest.MonkeyPatch())
    n_ctl = len(args.seeds) if args.control_seeds is None else args.control_seeds
    for i, seed in enumerate(args.seeds):
        keep: dict = {}
        res = harness.run(args.workload, seed, args.seconds, False,
                          t_process=time.perf_counter(), cell=cell,
                          devices=devices, keep=keep,
                          log=lambda s: print(s, file=sys.stderr))
        row = {"seed": seed, "fault": args.fault, "correct": res["correct"],
               "program": numbers(keep["gaps"]), "checks": res["checks"],
               "metrics": {k: v["value"] for k, v in res["metrics"].items()}}
        if i < n_ctl:
            t0 = time.perf_counter()
            g = reference.control_gaps(keep["params"], cell.config["config"],
                                       cell.config["cache"], keep["seqs"])
            checks, ok = harness.judge(cell.check, g)
            row.update(control=numbers(g), control_checks=checks,
                       control_correct=bool(ok and keep["rest_ok"]),
                       control_s=time.perf_counter() - t0)
        print(json.dumps(row), flush=True)
        del keep, res
        gc.collect()        # the run's engine holds cycles: free its weights
    return 0


if __name__ == "__main__":
    sys.exit(main())
