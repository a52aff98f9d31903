#!/usr/bin/env python3
"""Run the sharded bank-transfer cell's controls at the cell's own size,
after a real short window: `benchmark/tools/control_transfer.py`'s twin for
Savina's Bank Transaction over four chips.

    python3 benchmark/tools/control_xtransfer.py --seed 7 --seconds 10

on a host with four TPUs.

One process: the cell runs as `benchmark/run.py` runs it (the program's own
verdict is printed), then every control of
benchmark/reference/bank_transfer_sharded_controls.py is judged on the chunk
after that window. Exit code 0 means the program came out correct, the
unbroken reference in its place came out correct, AND every control came
out not correct by the limits named for it and no other."""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="xshard-transfer-4chip")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    from benchmark import harness
    from benchmark.reference.bank_transfer_sharded_controls import (
        caught_as_named)

    res = harness.execute(args.workload, args.seed, args.seconds, False,
                          T_START, faults={"controls": True})
    line = {"workload": args.workload, "seed": args.seed,
            "program_correct": res["correct"],
            "program": {k: c["value"] for k, c in res["compared"].items()},
            "counters": res["setup_parts_s"].get("xtransfer"),
            "controls": {}}
    ok = res["correct"]
    for name, numbers in res["controls"].items():
        failed = {k: c["value"] for k, c in numbers.items()
                  if c["value"] > c["limit"]}
        as_named = not failed if name == "reference_itself" \
            else caught_as_named(name, numbers)
        line["controls"][name] = {"correct": not failed,
                                  "failed_numbers": failed,
                                  "as_named": as_named}
        ok = ok and as_named
    print(json.dumps(line), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
