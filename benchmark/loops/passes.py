"""The pass loop: a backlog decided in whole passes.

A pass cancels every workload of the last pass, admitted or pending,
sends a fresh backlog of the configuration's full size, round-robin over
the LocalQueues, in one batch POST, and reconciles the server until
quiescent. A traffic file with ``"loop": "passes"`` sets
``warmup_passes``: passes run in set-up, after the flavors,
ClusterQueues and LocalQueues are loaded, so every shape the window uses
is compiled before it; and ``device_warmup_passes``: set-up passes after
those, with every cycle decided on the device (see the harness).

Each pass's batch is generated before its clock starts, and its
decisions are read after the clock stops: a pass's ``served_s`` holds
only the calls into the server.
Every decision is logged for the reference: what left, what arrived,
and what held quota once the pass settled.
"""

from __future__ import annotations

import json
import time
from typing import List, Tuple


class PassLoop:
    def __init__(self, config: dict, traffic: dict, seed: int, generator):
        self.config = config
        self.generator = generator
        self.body = generator.deployment(config, seed)
        self.stream = generator.WorkloadStream(
            config, seed, prefix="pw", first_time=0.0
        )
        self.live: List[str] = []
        # the decision log: (left, arrived, admitted after) per pass;
        # entry 0 is the loaded configuration. Each entry is kept as a few
        # flat strings, so the log adds next to nothing to what the
        # garbage collector scans while the window runs.
        self.log: List[Tuple[tuple, str, tuple]] = []

    def load(self, system) -> None:
        system.load(self.body)
        system.settle()
        self.log.append(((), json.dumps(self.body), _decisions(system.admitted())))
        self.body = None

    def one_pass(self, system) -> dict:
        """One pass; returns what it admitted, what it sent, and the
        seconds spent in the server."""
        lqs = self.generator.backlog(self.config)
        payload, keys = self.stream.batch(lqs)
        left = tuple(self.live)
        t0 = time.perf_counter()
        system.remove(left)
        system.submit(payload, len(keys))
        system.settle()
        served_s = time.perf_counter() - t0
        self.live = keys
        after = system.admitted()
        self.log.append((left, payload, _decisions(after)))
        return {"admitted": len(after), "submitted": len(keys), "served_s": served_s}


def _decisions(admitted: dict) -> tuple:
    """Admissions as sorted ``key<TAB>ClusterQueue<TAB>flavors`` lines."""
    return tuple(sorted(f"{k}\t{v[0]}\t{v[1]!r}" for k, v in admitted.items()))


def replay(reference, log: List[tuple]) -> List[dict]:
    """Feed ``reference`` the logged events pass by pass, and compare
    what it admitted with what the program admitted after each pass.
    Stops after the first pass that differs: the program's later
    events follow from its own decisions. A pass in which a preemption
    is due, which the reference does not decide, counts every admission
    of the program's, and at least one, as mismatched."""
    out = []
    for i, (left, arrived, program) in enumerate(log):
        reference.delete(left)
        reference.apply(json.loads(arrived))
        try:
            reference.run()
        except RuntimeError as e:
            out.append({"pass": i, "program": len(program), "reference": None,
                        "mismatched": max(1, len(program)), "example": [str(e)]})
            break
        ref = _decisions(reference.admitted())
        wrong = set(program) ^ set(ref)
        out.append({
            "pass": i,
            "program": len(program),
            "reference": len(ref),
            "mismatched": len({line.split("\t", 1)[0] for line in wrong}),
            "example": sorted(wrong)[:2],
        })
        if wrong:
            break
    return out
