"""Plain reference of Kueue's admission for flat cohorts whose
ClusterQueues have several resource groups and either value of
``flavorFungibility.whenCanBorrow``, under the preemption policy
``Never``.

A straightforward, sequential implementation of what the scheduler of
upstream Kueue (``pkg/scheduler``, ``pkg/queue``,
``pkg/scheduler/flavorassigner``) decides, written from that description
and independent of the program under test: it imports nothing of it and
reads only the wire objects the benchmark generated. It extends
``kueue_classic`` (one resource group, ``whenCanBorrow: Borrow``):

- Every ClusterQueue keeps an active heap (priority high to low, then
  creation time) and a parking lot of inadmissible workloads
  (BestEffortFIFO). A cycle pops the head of every ClusterQueue.
- A head walks each resource group its requests touch on its own, over
  the group's flavors, from the flavor after the one its last attempt
  stopped at in that group (a cursor per group, as ``LastAssignment``
  keeps one per resource), against the usage at the start of the cycle.
  A flavor's mode is the worst of its resources' modes: it fits when the
  request fits the available quota, ``min(nominal + borrowingLimit -
  used, cohortNominal - cohortUsed)``; a request within nominal quota
  that does not fit is "reclaim" when the queue stays within nominal,
  else "preempt"; anything else does not fit. The flavor borrows when
  any of its resources would take the queue above nominal.
- The walk of a group stops at a flavor that fits without borrowing, or
  that fits by borrowing when ``whenCanBorrow`` is ``Borrow``
  (``flavorassigner.go:620-638``); otherwise it runs to the group's end
  and keeps the first flavor of the highest mode seen. The group's
  cursor is the flavor the walk stopped at, or none (restart at the
  first) when that is the group's last flavor or the walk ran to the
  end. A group in which no flavor fits even by preemption fails the
  head, and clears every cursor.
- The head's mode is the worst of its groups'. Heads are then taken in
  order: not borrowing before borrowing, then priority, then creation
  time. A head that still fits is admitted. A preempt or reclaim mode
  head has no one to preempt (``Never``) and reserves the queue's
  remaining quota for the rest of the cycle, as Kueue's
  ``resourcesToReserve`` does.
- After the cycle, a head that fitted but lost its quota to an earlier
  head goes straight back to its heap, and so does a preempt-mode head
  whose walk left a cursor in some group (PendingFlavors); both resume
  from their cursors. Any other head is parked and restarts at the
  first flavor of every group.
- Freeing quota in a CQ (an admitted workload is deleted) returns every
  parked workload of its cohort to the heaps.

``run()`` runs cycles until no ClusterQueue has an active head.
"""

from __future__ import annotations

import heapq
from typing import Dict, Iterable, List, Optional, Tuple

from benchmark.references.kueue_classic import (
    FIT,
    NO_FIT,
    PREEMPT,
    RECLAIM,
    quantity,
)

_BORROW = ("Borrow", "TryNextFlavor")

Cell = Tuple[str, str]  # (flavor, resource)


class _CQ:
    __slots__ = ("name", "cohort", "groups", "nominal", "limit", "used",
                 "heap", "parked", "admitted", "borrow_policy")

    def __init__(self, obj: dict):
        self.name = obj["name"]
        self.cohort = obj.get("cohort") or None
        prem = obj.get("preemption") or {}
        for key in ("withinClusterQueue", "reclaimWithinCohort"):
            if prem.get(key, "Never") != "Never":
                raise ValueError(f"{self.name}: only preemption Never, got {prem}")
        if prem.get("borrowWithinCohort"):
            raise ValueError(f"{self.name}: borrowWithinCohort unsupported")
        ff = obj.get("flavorFungibility") or {}
        self.borrow_policy = ff.get("whenCanBorrow", "Borrow")
        if self.borrow_policy not in _BORROW:
            raise ValueError(f"{self.name}: whenCanBorrow {self.borrow_policy!r}")
        if ff.get("whenCanPreempt", "TryNextFlavor") != "TryNextFlavor":
            raise ValueError(f"{self.name}: whenCanPreempt {ff['whenCanPreempt']!r}")
        # per group: (covered resources, flavor names in walk order)
        self.groups: List[Tuple[Tuple[str, ...], List[str]]] = []
        self.nominal: Dict[Cell, int] = {}
        # nominal + borrowingLimit, None without a limit
        self.limit: Dict[Cell, Optional[int]] = {}
        seen = set()
        for g in obj.get("resourceGroups", []):
            names = []
            for fq in g["flavors"]:
                if fq["name"] in seen:
                    raise ValueError(f"{self.name}: flavor {fq['name']} in two groups")
                seen.add(fq["name"])
                names.append(fq["name"])
                for r in fq["resources"]:
                    if "lendingLimit" in r:
                        raise ValueError(f"{self.name}: lending limits unsupported")
                    cell = (fq["name"], r["name"])
                    n = quantity(r["name"], r["nominalQuota"])
                    self.nominal[cell] = n
                    b = r.get("borrowingLimit")
                    self.limit[cell] = None if b is None else n + quantity(r["name"], b)
            self.groups.append((tuple(g["coveredResources"]), names))
        self.used: Dict[Cell, int] = {cell: 0 for cell in self.nominal}
        self.heap: list = []
        self.parked: Dict[str, "_Workload"] = {}
        self.admitted: Dict[str, "_Workload"] = {}


class _Workload:
    __slots__ = ("key", "cq", "priority", "created", "pod_set", "request",
                 "start", "flavors", "order")

    def __init__(self, obj: dict, cq: str, order: int):
        self.key = f"{obj['namespace']}/{obj['name']}"
        self.cq = cq
        self.priority = int(obj.get("priority", 0))
        self.created = float(obj.get("creationTime", 0.0))
        pod_sets = obj["podSets"]
        if len(pod_sets) != 1:
            raise ValueError(f"{self.key}: one pod set expected")
        ps = pod_sets[0]
        self.pod_set = ps["name"]
        n = int(ps.get("count", 1))
        self.request = {
            r: quantity(r, q) * n for r, q in ps.get("requests", {}).items()
        }
        self.start: Dict[int, int] = {}  # group -> flavor index to start at
        self.flavors: Optional[Dict[str, str]] = None  # resource -> flavor
        self.order = order

    def rank(self) -> tuple:
        return (-self.priority, int(self.created * 1e9), self.order)


class _Walk:
    """One head's walk of every group it touches."""

    __slots__ = ("mode", "flavors", "borrow", "cursor")

    def __init__(self, mode, flavors, borrow, cursor):
        self.mode = mode  # the worst of its groups' modes
        self.flavors: Dict[str, str] = flavors  # resource -> flavor
        self.borrow: bool = borrow
        self.cursor: Dict[int, int] = cursor  # group -> next start

    def pending(self) -> bool:
        return any(s > 0 for s in self.cursor.values())


class Reference:
    """The reference state: apply objects, delete workloads, run. The
    benchmark finds a configuration's reference by this class name."""

    def __init__(self, break_guarantee: Optional[str] = None,
                 force_borrow: bool = False):
        # the controls. "cohort_pool" (benchmark.control) ignores what the
        # cohort's other members use: each ClusterQueue borrows up to its
        # borrowing limit, or without one up to its cohort's whole
        # nominal quota. ``force_borrow`` walks every ClusterQueue as
        # whenCanBorrow Borrow, whatever it states.
        if break_guarantee not in (None, "cohort_pool"):
            raise ValueError(f"unknown guarantee {break_guarantee!r}")
        self.break_guarantee = break_guarantee
        self.force_borrow = force_borrow
        self.cqs: Dict[str, _CQ] = {}
        self.lqs: Dict[str, str] = {}
        self.workloads: Dict[str, _Workload] = {}
        self.cohort_nominal: Dict[str, Dict[Cell, int]] = {}
        self.cohort_used: Dict[str, Dict[Cell, int]] = {}
        self.cohort_members: Dict[str, List[str]] = {}
        self._order = 0

    # ---- objects ----
    def apply(self, body: dict) -> None:
        for obj in body.get("clusterqueues", []):
            cq = _CQ(obj)
            self.cqs[cq.name] = cq
            if cq.cohort is not None:
                self.cohort_members.setdefault(cq.cohort, []).append(cq.name)
        for obj in body.get("localqueues", []):
            self.lqs[f"{obj['namespace']}/{obj['name']}"] = obj["clusterQueue"]
        if body.get("clusterqueues"):
            self._cohort_totals()
        for obj in body.get("workloads", []):
            cq = self.lqs[f"{obj['namespace']}/{obj['queueName']}"]
            wl = _Workload(obj, cq, self._order)
            self._order += 1
            self.workloads[wl.key] = wl
            heapq.heappush(self.cqs[cq].heap, (wl.rank(), wl.key))

    def _cohort_totals(self) -> None:
        for cohort, names in self.cohort_members.items():
            nom: Dict[Cell, int] = {}
            used: Dict[Cell, int] = {}
            for name in names:
                cq = self.cqs[name]
                for cell, n in cq.nominal.items():
                    nom[cell] = nom.get(cell, 0) + n
                    used[cell] = used.get(cell, 0) + cq.used[cell]
            self.cohort_nominal[cohort] = nom
            self.cohort_used[cohort] = used

    def delete(self, keys: Iterable[str]) -> None:
        """Workloads leave: an admitted one releases its quota, and the
        parked workloads of every cohort that gained quota return to
        their heaps; a pending one just leaves its queue."""
        touched = set()
        for key in keys:
            wl = self.workloads.pop(key)
            cq = self.cqs[wl.cq]
            if wl.flavors is None:
                cq.parked.pop(key, None)  # a heap entry is skipped on pop
                continue
            self._charge(cq, wl.flavors, wl.request, -1)
            del cq.admitted[key]
            touched.add(cq.cohort or wl.cq)
        for cohort in touched:
            for name in self.cohort_members.get(cohort, [cohort]):
                cq = self.cqs[name]
                for wl in cq.parked.values():
                    heapq.heappush(cq.heap, (wl.rank(), wl.key))
                cq.parked.clear()

    def admitted(self) -> Dict[str, Tuple[str, tuple]]:
        """(ClusterQueue, ((pod set, resource, flavor), ...)) by key of
        every admitted workload."""
        return {
            k: (wl.cq, tuple(sorted((wl.pod_set, r, f) for r, f in wl.flavors.items())))
            for k, wl in self.workloads.items()
            if wl.flavors is not None
        }

    # ---- quota ----
    def _charge(self, cq: _CQ, flavors: Dict[str, str], amounts: Dict[str, int],
                sign: int) -> None:
        for r, v in amounts.items():
            cell = (flavors[r], r)
            cq.used[cell] += sign * v
            if cq.cohort is not None:
                self.cohort_used[cq.cohort][cell] += sign * v

    def _available(self, cq: _CQ, cell: Cell) -> int:
        if cq.cohort is None:
            return cq.nominal[cell] - cq.used[cell]
        lim = cq.limit[cell]
        if self.break_guarantee == "cohort_pool":
            cap = self.cohort_nominal[cq.cohort][cell] if lim is None else lim
            return cap - cq.used[cell]
        pool = self.cohort_nominal[cq.cohort][cell] - self.cohort_used[cq.cohort][cell]
        return pool if lim is None else min(lim - cq.used[cell], pool)

    def _capacity(self, cq: _CQ, cell: Cell) -> int:
        if cq.cohort is None:
            return cq.nominal[cell]
        pool = self.cohort_nominal[cq.cohort][cell]
        lim = cq.limit[cell]
        return pool if lim is None else min(lim, pool)

    def _flavor_mode(self, cq: _CQ, f: str, request: Dict[str, int]) -> Tuple[int, bool]:
        """(mode, borrowing) of one flavor for a group's requests."""
        mode, borrow = FIT, False
        for r, v in request.items():
            cell = (f, r)
            if cell not in cq.nominal:
                return NO_FIT, borrow
            nominal = cq.nominal[cell]
            if cq.cohort is not None:
                borrow = borrow or cq.used[cell] + v > nominal
            if v > self._capacity(cq, cell):
                return NO_FIT, borrow
            if v <= max(0, self._available(cq, cell)):
                continue
            if v > nominal:
                return NO_FIT, borrow
            mode = min(mode, RECLAIM if cq.used[cell] + v <= nominal else PREEMPT)
        return mode, borrow

    def _walk_group(self, cq: _CQ, wl: _Workload, g: int) -> Tuple[int, Optional[str], bool, int]:
        """(mode, flavor, borrowing, next start) of group ``g``'s walk."""
        resources, flavors = cq.groups[g]
        request = {r: v for r, v in wl.request.items() if r in resources}
        stop_on_borrow = self.force_borrow or cq.borrow_policy == "Borrow"
        best_mode, best, best_borrow = NO_FIT, None, False
        last = len(flavors) - 1
        for k in range(wl.start.get(g, 0), len(flavors)):
            mode, borrow = self._flavor_mode(cq, flavors[k], request)
            if mode == FIT and (not borrow or stop_on_borrow):
                return FIT, flavors[k], borrow, 0 if k == last else k + 1
            if mode > best_mode:
                best_mode, best, best_borrow = mode, flavors[k], borrow
        return best_mode, best, best_borrow, 0

    def _walk(self, cq: _CQ, wl: _Workload) -> _Walk:
        covered = {r: g for g, (resources, _) in enumerate(cq.groups) for r in resources}
        if any(r not in covered for r in wl.request):
            return _Walk(NO_FIT, {}, False, {})
        # groups in the order of their first requested resource, as
        # the flavor assigner takes resources in sorted order
        order: List[int] = []
        for r in sorted(wl.request):
            if covered[r] not in order:
                order.append(covered[r])
        head_mode, flavors, borrow, cursor = FIT, {}, False, {}
        for g in order:
            mode, f, b, nxt = self._walk_group(cq, wl, g)
            if f is None:
                return _Walk(NO_FIT, {}, False, {})
            head_mode = min(head_mode, mode)
            borrow = borrow or b
            cursor[g] = nxt
            for r in wl.request:
                if covered[r] == g:
                    flavors[r] = f
        return _Walk(head_mode, flavors, borrow, cursor)

    def _reserve(self, cq: _CQ, walk: _Walk, wl: _Workload) -> Dict[str, int]:
        out = {}
        for r, v in wl.request.items():
            if v == 0:
                continue
            cell = (walk.flavors[r], r)
            if walk.borrow:
                lim = cq.limit[cell]
                out[r] = v if lim is None else min(v, lim - cq.used[cell])
            else:
                out[r] = max(0, min(v, cq.nominal[cell] - cq.used[cell]))
        return out

    # ---- the loop ----
    def cycle(self) -> int:
        """One scheduling cycle; returns the number of heads."""
        heads: List[Tuple[_CQ, _Workload]] = []
        for name in sorted(self.cqs):
            cq = self.cqs[name]
            while cq.heap:
                _, key = heapq.heappop(cq.heap)
                wl = self.workloads.get(key)
                if wl is not None:
                    heads.append((cq, wl))
                    break
        if not heads:
            return 0
        entries = [(cq, wl, self._walk(cq, wl)) for cq, wl in heads]
        order = sorted(
            range(len(entries)),
            key=lambda i: (
                entries[i][2].borrow, -entries[i][1].priority,
                int(entries[i][1].created * 1e9),
            ),
        )
        # reservations live for this cycle only
        reserved: List[Tuple[_CQ, Dict[str, str], Dict[str, int]]] = []
        done = set()
        for i in order:
            cq, wl, walk = entries[i]
            if walk.mode == NO_FIT:
                continue
            if walk.mode in (PREEMPT, RECLAIM):
                res = self._reserve(cq, walk, wl)
                self._charge(cq, walk.flavors, res, 1)
                reserved.append((cq, walk.flavors, res))
                continue
            if all(
                v <= self._available(cq, (walk.flavors[r], r))
                for r, v in wl.request.items() if v > 0
            ):
                self._charge(cq, walk.flavors, wl.request, 1)
                wl.flavors = dict(walk.flavors)
                wl.start = {}
                cq.admitted[wl.key] = wl
                done.add(i)
        for cq, flavors, res in reserved:
            self._charge(cq, flavors, res, -1)
        for i, (cq, wl, walk) in enumerate(entries):
            if i in done:
                continue
            if walk.mode == FIT or (walk.mode != NO_FIT and walk.pending()):
                # fitted and lost the quota to an earlier head, or a
                # walk with untried flavors left: back to the heap
                wl.start = walk.cursor
                heapq.heappush(cq.heap, (wl.rank(), wl.key))
            else:
                wl.start = {}
                cq.parked[wl.key] = wl
        return len(heads)

    def run(self, max_cycles: int = 1_000_000) -> int:
        """Cycles until no ClusterQueue has an active head."""
        for n in range(max_cycles):
            if not self.cycle():
                return n
        raise RuntimeError(f"not quiescent after {max_cycles} cycles")
