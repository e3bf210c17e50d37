"""Plain reference of Kueue's admission for flat cohorts under the
classic (not fair-sharing) preemption policies.

A straightforward, sequential implementation of what the scheduler of
upstream Kueue (``pkg/scheduler``, ``pkg/queue``,
``pkg/scheduler/flavorassigner``, ``pkg/scheduler/preemption``) decides,
written from that description and independent of the program under
test: it imports nothing of it and reads only the wire objects the
benchmark generated.

Semantics, cycle by cycle:

- Every ClusterQueue keeps an active heap (priority high to low, then
  creation time) and a parking lot of inadmissible workloads
  (BestEffortFIFO). A cycle pops the head of every ClusterQueue.
- Each head walks its CQ's flavors, from the flavor after the one it
  last fitted if its previous attempt was cut short, against the usage
  at the start of the cycle. A flavor fits when every resource fits the
  CQ's available quota: ``min(nominal + borrowingLimit - used,
  cohortNominal - cohortUsed)``. The first flavor that fits is taken,
  borrowing or not (``whenCanBorrow: Borrow``). A flavor whose request is
  within nominal quota but not available counts as "reclaim" when the
  CQ would stay within nominal quota, else as "preempt"; the walk goes
  on (``whenCanPreempt: TryNextFlavor``) and keeps the first flavor of
  the highest such mode.
- A head in a preempt or reclaim mode looks for candidates at the start
  of the cycle: with ``withinClusterQueue`` not ``Never``, admitted
  workloads of its own CQ on that flavor of lower priority (or, under
  ``LowerOrNewerEqualPriority``, of equal priority too); with
  ``reclaimWithinCohort`` not ``Never``, admitted workloads on that
  flavor in the cohort's other CQs that borrow it (under
  ``LowerPriority``, of lower priority only). This reference decides no
  victims: where a candidate exists it stops with ``PreemptionDue``, so
  a comparison through it covers states in which no preemption is due.
  A head with no candidate admits nothing, and reserves the CQ's
  remaining quota for the rest of the cycle, as Kueue's
  ``resourcesToReserve`` does, unless ``reclaimWithinCohort`` is
  ``Any`` (``CanAlwaysReclaim``).
- Heads are then taken in order: not borrowing before borrowing, then
  priority, then creation time. A head that still fits is admitted; one
  that no longer fits goes straight back to its heap and resumes its
  walk at the next flavor; one that fits nowhere is parked.
- Freeing quota in a CQ (an admitted workload is deleted) returns every
  parked workload of its cohort to the heaps.

``run()`` runs cycles until no ClusterQueue has an active head.
"""

from __future__ import annotations

import heapq
import re
from typing import Dict, Iterable, List, Optional, Tuple

_SUFFIX = {
    "": 1, "k": 10**3, "M": 10**6, "G": 10**9, "T": 10**12,
    "Ki": 2**10, "Mi": 2**20, "Gi": 2**30, "Ti": 2**40,
}
_QTY = re.compile(r"^([0-9]+)(m|k|M|G|T|Ki|Mi|Gi|Ti)?$")

# a flavor's mode, lowest to highest preference; "reclaim" is a preempt
# within nominal quota that would take only what the cohort borrowed
FIT, RECLAIM, PREEMPT, NO_FIT = 3, 2, 1, 0

_WITHIN = ("Never", "LowerPriority", "LowerOrNewerEqualPriority")
_RECLAIM = ("Never", "LowerPriority", "Any")


class PreemptionDue(RuntimeError):
    """A head has a preemption candidate; this reference decides no
    victims."""


def quantity(resource: str, text) -> int:
    """A Kubernetes quantity in the reference's units: milli-cpu for
    ``cpu``, the plain number for everything else."""
    m = _QTY.match(str(text))
    if m is None:
        raise ValueError(f"unsupported quantity {text!r}")
    value, suffix = int(m.group(1)), m.group(2) or ""
    if suffix == "m":
        if resource != "cpu":
            raise ValueError(f"milli quantity {text!r} for {resource}")
        return value
    scaled = value * _SUFFIX[suffix]
    return scaled * 1000 if resource == "cpu" else scaled


class _CQ:
    __slots__ = ("name", "cohort", "flavors", "resources", "nominal",
                 "limit", "used", "heap", "parked", "admitted", "within",
                 "reclaim")

    def __init__(self, obj: dict):
        self.name = obj["name"]
        self.cohort = obj.get("cohort") or None
        prem = obj.get("preemption") or {}
        self.within = prem.get("withinClusterQueue", "Never")
        self.reclaim = prem.get("reclaimWithinCohort", "Never")
        if self.within not in _WITHIN or self.reclaim not in _RECLAIM:
            raise ValueError(f"{self.name}: unsupported preemption {prem}")
        if prem.get("borrowWithinCohort"):
            raise ValueError(f"{self.name}: borrowWithinCohort unsupported")
        groups = obj.get("resourceGroups", [])
        if len(groups) != 1:
            raise ValueError(f"{self.name}: one resource group expected")
        g = groups[0]
        self.resources = tuple(g["coveredResources"])
        self.flavors: List[str] = []
        # per flavor, per resource: nominal, nominal + borrowingLimit
        self.nominal: List[Dict[str, int]] = []
        self.limit: List[Dict[str, Optional[int]]] = []
        for fq in g["flavors"]:
            self.flavors.append(fq["name"])
            nom, lim = {}, {}
            for r in fq["resources"]:
                if "lendingLimit" in r:
                    raise ValueError(f"{self.name}: lending limits unsupported")
                n = quantity(r["name"], r["nominalQuota"])
                nom[r["name"]] = n
                b = r.get("borrowingLimit")
                lim[r["name"]] = None if b is None else n + quantity(r["name"], b)
            self.nominal.append(nom)
            self.limit.append(lim)
        self.used = [{r: 0 for r in self.resources} for _ in self.flavors]
        self.heap: list = []
        self.parked: Dict[str, "_Workload"] = {}
        self.admitted: Dict[str, "_Workload"] = {}


class _Workload:
    __slots__ = ("key", "cq", "priority", "created", "pod_set", "request",
                 "start", "flavor", "order")

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
        self.start = 0  # flavor index the next walk starts from
        self.flavor: Optional[int] = None  # admitted flavor index
        self.order = order

    def rank(self) -> tuple:
        return (-self.priority, int(self.created * 1e9), self.order)


class Reference:
    """The reference state: apply objects, delete workloads, run. The
    benchmark finds a configuration's reference by this class name."""

    def __init__(self, break_guarantee: Optional[str] = None):
        # the control: "cohort_pool" lets every ClusterQueue borrow up to
        # its borrowing limit whatever its cohort has left, a guarantee
        # the configuration states; with it the reference does not stop
        # where a preemption is due, it admits nothing there
        if break_guarantee not in (None, "cohort_pool"):
            raise ValueError(f"unknown guarantee {break_guarantee!r}")
        self.break_guarantee = break_guarantee
        self.cqs: Dict[str, _CQ] = {}
        self.lqs: Dict[str, str] = {}
        self.workloads: Dict[str, _Workload] = {}
        self.cohort_nominal: Dict[str, List[Dict[str, int]]] = {}
        self.cohort_used: Dict[str, List[Dict[str, int]]] = {}
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
            first = self.cqs[names[0]]
            nom = [{r: 0 for r in first.resources} for _ in first.flavors]
            used = [{r: 0 for r in first.resources} for _ in first.flavors]
            for name in names:
                cq = self.cqs[name]
                if cq.flavors != first.flavors or cq.resources != first.resources:
                    raise ValueError(f"cohort {cohort}: CQs differ in flavors")
                for k in range(len(cq.flavors)):
                    for r in cq.resources:
                        nom[k][r] += cq.nominal[k][r]
                        used[k][r] += cq.used[k][r]
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
            if wl.flavor is None:
                cq.parked.pop(key, None)  # a heap entry is skipped on pop
                continue
            self._charge(cq, wl.flavor, wl.request, -1)
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
        out = {}
        for k, wl in self.workloads.items():
            if wl.flavor is not None:
                f = self.cqs[wl.cq].flavors[wl.flavor]
                out[k] = (
                    wl.cq, tuple(sorted((wl.pod_set, r, f) for r in wl.request))
                )
        return out

    # ---- quota ----
    def _charge(self, cq: _CQ, k: int, request: Dict[str, int], sign: int) -> None:
        for r, v in request.items():
            cq.used[k][r] += sign * v
            if cq.cohort is not None:
                self.cohort_used[cq.cohort][k][r] += sign * v

    def _available(self, cq: _CQ, k: int, r: str, used, cohort_used) -> int:
        if cq.cohort is None:
            return cq.nominal[k][r] - used[k][r]
        lim = cq.limit[k][r]
        if self.break_guarantee == "cohort_pool" and lim is not None:
            return lim - used[k][r]
        pool = self.cohort_nominal[cq.cohort][k][r] - cohort_used[k][r]
        return pool if lim is None else min(lim - used[k][r], pool)

    def _walk(self, cq: _CQ, wl: _Workload) -> Tuple[int, Optional[int], bool, int]:
        """(mode, flavor index, borrowing, next start) of one walk."""
        used = cq.used
        cohort_used = self.cohort_used.get(cq.cohort)
        best_mode, best, best_borrow = NO_FIT, None, False
        for k in range(wl.start, len(cq.flavors)):
            mode, borrow = FIT, False
            for r, v in wl.request.items():
                if r not in cq.nominal[k]:
                    mode = NO_FIT
                    break
                nominal = cq.nominal[k][r]
                if cq.cohort is not None:
                    borrow = borrow or used[k][r] + v > nominal
                    pool = self.cohort_nominal[cq.cohort][k][r]
                    lim = cq.limit[k][r]
                    capacity = pool if lim is None else min(lim, pool)
                    if self.break_guarantee == "cohort_pool" and lim is not None:
                        capacity = lim
                else:
                    capacity = nominal
                if v > capacity:
                    mode = NO_FIT
                    break
                if v <= max(0, self._available(cq, k, r, used, cohort_used)):
                    continue
                if v <= nominal:
                    mode = min(mode, RECLAIM if used[k][r] + v <= nominal else PREEMPT)
                else:
                    mode = NO_FIT
                    break
            if mode == FIT:
                nxt = 0 if k == len(cq.flavors) - 1 else k + 1
                return FIT, k, borrow, nxt
            if mode > best_mode:
                best_mode, best, best_borrow = mode, k, borrow
        return best_mode, best, best_borrow, 0

    def _candidate(self, cq: _CQ, k: int, wl: _Workload) -> Optional[str]:
        """A workload the head could preempt on flavor ``k``, if any."""
        if cq.within != "Never":
            for c in cq.admitted.values():
                if c.flavor == k and (
                    c.priority < wl.priority
                    or (cq.within == "LowerOrNewerEqualPriority"
                        and c.priority == wl.priority and c.created > wl.created)
                ):
                    return c.key
        if cq.reclaim == "Never" or cq.cohort is None:
            return None
        for name in self.cohort_members[cq.cohort]:
            other = self.cqs[name]
            if other is cq or not any(
                other.used[k][r] > other.nominal[k][r] for r in wl.request
            ):
                continue
            for c in other.admitted.values():
                if c.flavor == k and (cq.reclaim == "Any" or c.priority < wl.priority):
                    return c.key
        return None

    def _reserve(self, cq: _CQ, k: int, wl: _Workload,
                 borrowing: bool) -> Dict[str, int]:
        out = {}
        for r, v in wl.request.items():
            if v == 0:
                continue
            if borrowing:
                lim = cq.limit[k][r]
                out[r] = v if lim is None else min(v, lim - cq.used[k][r])
            else:
                out[r] = max(0, min(v, cq.nominal[k][r] - cq.used[k][r]))
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
        entries = []
        for cq, wl in heads:
            mode, k, borrow, nxt = self._walk(cq, wl)
            wl.start = nxt
            if mode in (PREEMPT, RECLAIM) and self.break_guarantee is None:
                victim = self._candidate(cq, k, wl)
                if victim is not None:
                    raise PreemptionDue(f"{wl.key} in {cq.name} could preempt {victim}")
            entries.append((cq, wl, mode, k, borrow))
        order = sorted(
            range(len(entries)),
            key=lambda i: (
                entries[i][4], -entries[i][1].priority,
                int(entries[i][1].created * 1e9),
            ),
        )
        # reservations live for this cycle only
        reserved: List[Tuple[_CQ, int, Dict[str, int]]] = []
        done = set()
        for i in order:
            cq, wl, mode, k, borrow = entries[i]
            if mode == NO_FIT:
                continue
            if mode in (PREEMPT, RECLAIM):
                if cq.reclaim != "Any":
                    res = self._reserve(cq, k, wl, borrow)
                    self._charge(cq, k, res, 1)
                    reserved.append((cq, k, res))
                continue
            cohort_used = self.cohort_used.get(cq.cohort)
            if all(
                v <= self._available(cq, k, r, cq.used, cohort_used)
                for r, v in wl.request.items() if v > 0
            ):
                self._charge(cq, k, wl.request, 1)
                wl.flavor = k
                wl.start = 0
                cq.admitted[wl.key] = wl
                done.add(i)
        for cq, k, res in reserved:
            self._charge(cq, k, res, -1)
        for i, (cq, wl, mode, k, borrow) in enumerate(entries):
            if i in done:
                continue
            if mode == FIT:
                # fitted, then lost the quota to an earlier head
                heapq.heappush(cq.heap, (wl.rank(), wl.key))
            else:
                wl.start = 0
                cq.parked[wl.key] = wl
        return len(heads)

    def run(self, max_cycles: int = 1_000_000) -> int:
        """Cycles until no ClusterQueue has an active head."""
        for n in range(max_cycles):
            if not self.cycle():
                return n
        raise RuntimeError(f"not quiescent after {max_cycles} cycles")
