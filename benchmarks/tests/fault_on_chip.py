"""A cell at its own size on the chip with one fault of ``faults.py``
planted in the program: the run has to end in ``correct`` false.

    python3 -m benchmarks.tests.fault_on_chip --fault token_altered \\
        --workload mistral_7b_serve.decode --seed <n> --seconds <s>

Prints the result line of ``benchmarks.run`` and exits 0 when the fault
was caught, 1 when the run came out correct. By hand, never by the
driver."""

import argparse
import json
import sys

from benchmarks import run
from benchmarks.tests import faults


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fault", required=True, choices=sorted(faults.FAULTS))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    try:
        bench, cell, cfg, spec = run.load_cell(args.workload)
        with faults.FAULTS[args.fault]():
            result = run.run_cell(bench, cell, cfg, spec, args.seed,
                                  args.seconds, False)
    except run.Refused as e:
        print(f"fault_on_chip: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    print(f"fault {args.fault}: correct {result['correct']} "
          f"(has to be false)", file=sys.stderr)
    return int(bool(result["correct"]))


if __name__ == "__main__":
    sys.exit(main())
