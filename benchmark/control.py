"""The control: the reference with one guarantee broken, in the
program's place.

The configuration states that each ClusterQueue borrows only what its
cohort has left of its members' nominal quotas. The control is the
configuration's plain reference with that pool ignored (each
ClusterQueue borrows up to its borrowing limit), driven through the cell's own pass
loop at the cell's own size exactly as the server is, and then judged by
the same comparison with the intact reference that decides ``correct``.
It has to come out not correct: its ``mismatched_admissions`` is the
upper reading the limit is set below.

    python3 -m benchmark.control --workload <cell> --seeds 1,2,3 --passes 3
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from typing import Dict, Optional, Sequence

from benchmark.registry import Registry


class ReferenceSystem:
    """A reference standing where the server stands in the pass loop."""

    def __init__(self, reference):
        self.ref = reference
        self.rejected = 0
        self.reset_counters()

    def reset_counters(self) -> None:
        self.spans: Dict[str, float] = {}
        self.counts: Dict[str, int] = {}
        self.cycles: list = []
        self.cycles_lost = False

    def load(self, body: dict) -> None:
        self.ref.apply(body)

    def submit(self, payload: str, count: int) -> None:
        self.ref.apply(json.loads(payload))

    def settle(self) -> None:
        self.ref.run()

    def remove(self, keys: Sequence[str]) -> None:
        self.ref.delete(keys)

    def admitted(self) -> Dict[str, tuple]:
        return {k: (cq, fl, None) for k, (cq, fl) in self.ref.admitted().items()}

    def force_device(self, on: bool) -> None:
        pass

    def gate_estimates(self) -> dict:
        return {}

    def guard_health(self) -> dict:
        return {}

    def close(self) -> None:
        pass


def run_control(workload: str, seed: int, passes: int,
                registry: Optional[Registry] = None,
                guarantee: str = "cohort_pool") -> dict:
    reg = registry or Registry()
    cell = reg.cell(workload)
    config, traffic = cell["config"], cell["traffic"]
    generator = reg.module("generators", config["generator"])
    loops = reg.module("loops", traffic["loop"])
    references = reg.module("references", config["reference"])
    loop = loops.PassLoop(config, traffic, seed, generator)
    system = ReferenceSystem(references.Reference(break_guarantee=guarantee))
    t0 = time.perf_counter()
    loop.load(system)
    for _ in range(passes):
        loop.one_pass(system)
    compared = loops.replay(references.Reference(), loop.log)
    return {
        "workload": workload,
        "seed": seed,
        "guarantee broken": guarantee,
        "passes": len(loop.log),
        "mismatched_admissions": sum(c["mismatched"] for c in compared),
        "first mismatched pass": next(
            (c["pass"] for c in compared if c["mismatched"]), None
        ),
        "seconds": time.perf_counter() - t0,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python3 -m benchmark.control")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--passes", type=int, default=3)
    args = ap.parse_args(argv)
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(run_control(args.workload, seed, args.passes)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
