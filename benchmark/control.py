"""Read the controls of a cell on the chip, at the cell's own size.

    python3 -m benchmark.control --workload <name> --seeds 1,2,3 --seconds <s>

For each seed: one short run of the cell as ``benchmark.run`` makes it
(same set-up, window, sink and comparison), whose own numbers are the
program's reading; then each control of the flow (the plain reference
put in the program's place, computed in the precision below the one
the configuration states, or with one stated guarantee broken) is
judged by the same comparison over the rows that run served.  Prints
one JSON line per seed.  The benchmark's own runs never run this; the
limits in the configuration files were set from its readings
(``PERF.md``).
"""

import argparse
import json
import sys
from typing import Any, Dict, List, Optional

from benchmark import run


def control_numbers(cell: run.Cell, line: Dict[str, Any]) -> Dict[str, Dict[str, float]]:
    """Each control's numbers, by the cell's own comparison."""
    internals = line["_run"]
    flow, cfg = cell.flow, cell.cfg
    # What the reference is made from: the job's data, or the stream
    # with the number of rows that the run served.
    basis = internals["basis"]
    want = flow.reference(cfg, *basis)
    return {
        which: flow.compare(cfg, flow.control_results(cfg, *basis, which), want)
        for which in flow.CONTROLS
    }


def failed_by(cell: run.Cell, numbers: Dict[str, float]) -> List[str]:
    limits = cell.cfg["limits"]
    return [k for k, v in numbers.items() if v > limits[k]]


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--seconds", type=float, required=True)
    args = parser.parse_args(argv)
    try:
        cell = run.Cell(args.workload)
        device = run.device_or_fail(cell.chips)
    except run.BenchFailure as ex:
        print(f"benchmark.control: {ex}", file=sys.stderr)
        return 1
    for seed in (int(s) for s in args.seeds.split(",")):
        line = run.run_cell(cell, seed, args.seconds, False, device)
        controls = control_numbers(cell, line)
        print(
            json.dumps(
                {
                    "workload": cell.name,
                    "seed": seed,
                    "program": {k: v[0] for k, v in line["checks"].items()},
                    "program_correct": line["correct"],
                    "controls": controls,
                    "controls_failed_by": {
                        which: failed_by(cell, numbers)
                        for which, numbers in controls.items()
                    },
                    "info": line["info"],
                }
            ),
            flush=True,
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
