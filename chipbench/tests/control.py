"""The control at a cell's own size: the reference with one guarantee
broken, run through the harness in the program's place, on several
seeds in one process.  Prints each seed's ``wrong_answers``.

    python3 chipbench/tests/control.py --workload <cell> --seconds <s> \
        --mode lag|no_rdel --seeds <n> [<n> ...]
"""

from __future__ import annotations

import argparse
import json

from common import run_once
from fakes import ControlEngine


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--mode", choices=("lag", "no_rdel"), required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    for seed in args.seeds:
        out = run_once(args.workload, seed, args.seconds, small=False,
                       engine=lambda: ControlEngine(args.mode))
        print(json.dumps({"workload": args.workload, "mode": args.mode,
                          "seed": seed, "correct": out["correct"],
                          "attempted": out["attempted"],
                          "wrong_answers":
                              out["compared"]["wrong_answers"]["value"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
