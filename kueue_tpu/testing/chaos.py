"""Gray-failure network chaos — latency-injecting transport wrappers.

Where ``FlakyTransport`` models fail-stop (down => every call fails
instantly), these wrappers model the *gray* failure modes the
latency health plane (federation/health.py) exists for: slow-but-alive
workers (:class:`LatencyTransport`), progressive slow-drip degradation
(:class:`SlowDripTransport`), and asymmetric loss — the mutation lands
but the ack never comes back (:class:`AsymmetricLossTransport`). Delay
is charged to the INJECTED clock (FakeClock in every chaos suite), so
a 9.9 s limp costs the dispatcher 9.9 simulated seconds without a
single real sleep — the deterministic convergence proofs keep running
at full speed.

Deadline interaction: each wrapper reads the per-call deadline the
RemoteClient threads onto the transport (``deadline_s``). A delay that
meets or exceeds the deadline is a timeout: the clock advances by the
full deadline and TransportError is raised — after the forward for
direction="response" (the exchange landed, the answer was lost),
instead of it for direction="request".

The fault points fired here (``chaos.latency``, ``chaos.drop_request``,
``chaos.drop_response``) are registered in ``testing.faults`` like
every other window the chaos suites can crash in.
"""

from __future__ import annotations

from typing import Callable, Optional

from kueue_tpu.testing import faults
def _transport_error(msg: str):
    # lazy import: faults is imported by nearly every module, so it
    # must not import the transport layer at module scope
    from kueue_tpu.admissionchecks.multikueue_transport import (
        TransportError,
    )

    return TransportError(msg)


class _ChaosTransport:
    """Shared forwarding shell for the chaos wrappers."""

    #: matches RemoteTransport.deadline_s threading — the RemoteClient
    #: sets the per-call deadline on the OUTERMOST transport; forward
    #: it inward so HTTPTransport still sees it under chaos.
    def __init__(self, inner, clock, default_deadline_s: float = 10.0):
        self.inner = inner
        self.clock = clock
        self.default_deadline_s = default_deadline_s
        self.calls = 0
        self.timeouts = 0

    @property
    def runtime(self):
        return self.inner.runtime

    @property
    def deadline_s(self):
        return getattr(self.inner, "deadline_s", None)

    @deadline_s.setter
    def deadline_s(self, value):
        self.inner.deadline_s = value

    def _effective_deadline(self) -> float:
        d = self.deadline_s
        return self.default_deadline_s if d is None else d

    def _exchange(self, name, *args):
        return getattr(self.inner, name)(*args)

    def get_workload(self, key):
        return self._exchange("get_workload", key)

    def create_workload(self, wl):
        return self._exchange("create_workload", wl)

    def create_workloads(self, wls):
        return self._exchange("create_workloads", wls)

    def delete_workload(self, key):
        return self._exchange("delete_workload", key)

    def list_workload_keys(self, origin):
        return self._exchange("list_workload_keys", origin)


class RecordingTransport(_ChaosTransport):
    """Passive shim: appends the injected-clock duration of every
    exchange (including ones that raise) to ``sink`` — wrap it OUTSIDE
    the chaos wrappers so the recorded latency is exactly what the
    dispatcher observed, injected delay and all."""

    def __init__(self, inner, clock, sink=None, default_deadline_s=10.0):
        super().__init__(inner, clock, default_deadline_s)
        self.sink = [] if sink is None else sink

    def _exchange(self, name, *args):
        self.calls += 1
        t0 = self.clock.now()
        try:
            return getattr(self.inner, name)(*args)
        finally:
            self.sink.append(self.clock.now() - t0)


class LatencyTransport(_ChaosTransport):
    """A limping worker: every exchange costs injected-clock time.

    - ``delay_s`` + ``jitter_s``: fixed or jittered per-call delay;
    - ``deadline_fraction``: delay tracks the CURRENT per-call
      deadline (0.99 = 'just under the deadline, every single call' —
      the canonical gray worker);
    - ``schedule``: callable ``now -> delay_s`` for flapping shapes
      (see :func:`flapping_schedule`);
    - ``direction``: where a too-long delay kills the exchange —
      'request' (never reaches the worker) or 'response' (lands, ack
      lost).
    """

    def __init__(
        self,
        inner,
        clock,
        delay_s: float = 0.0,
        jitter_s: float = 0.0,
        deadline_fraction: Optional[float] = None,
        schedule: Optional[Callable[[float], float]] = None,
        direction: str = "request",
        default_deadline_s: float = 10.0,
        rng=None,
    ):
        super().__init__(inner, clock, default_deadline_s)
        self.delay_s = delay_s
        self.jitter_s = jitter_s
        self.deadline_fraction = deadline_fraction
        self.schedule = schedule
        self.direction = direction
        self._rng = rng

    def _delay(self, now: float, deadline: float) -> float:
        if self.schedule is not None:
            base = float(self.schedule(now) or 0.0)
        elif self.deadline_fraction is not None:
            base = self.deadline_fraction * deadline
        else:
            base = self.delay_s
        if self.jitter_s and self._rng is not None:
            base += self.jitter_s * self._rng.random()
        return base

    def _exchange(self, name, *args):
        self.calls += 1
        faults.fire("chaos.latency")
        deadline = self._effective_deadline()
        delay = self._delay(self.clock.now(), deadline)
        if delay >= deadline:
            self.timeouts += 1
            if self.direction == "response":
                # the exchange LANDS before the deadline burns out
                getattr(self.inner, name)(*args)
            self.clock.advance(deadline)
            raise _transport_error(
                f"injected latency {delay:.3f}s exceeded deadline "
                f"{deadline:.3f}s"
            )
        self.clock.advance(delay)
        return getattr(self.inner, name)(*args)


class SlowDripTransport(LatencyTransport):
    """Progressive degradation: each call is slower than the last
    (``start_s + step_s * n``, capped at ``max_s``) — the disk-filling
    /-leaking worker that fails the way production actually fails."""

    def __init__(
        self,
        inner,
        clock,
        step_s: float = 0.5,
        start_s: float = 0.0,
        max_s: Optional[float] = None,
        **kw,
    ):
        super().__init__(inner, clock, **kw)
        self.step_s = step_s
        self.start_s = start_s
        self.max_s = max_s

    def _delay(self, now: float, deadline: float) -> float:
        base = self.start_s + self.step_s * (self.calls - 1)
        if self.max_s is not None:
            base = min(base, self.max_s)
        return base


class AsymmetricLossTransport(_ChaosTransport):
    """One-way loss: requests pass and responses drop (or vice
    versa), with probability ``p`` per exchange. The response
    direction is the hard one — the mutation LANDED, the caller sees
    a timeout, and only name+fence dedup / 404==ack retraction
    semantics keep the federation exactly-once."""

    def __init__(
        self,
        inner,
        clock,
        direction: str = "response",
        p: float = 1.0,
        rng=None,
        default_deadline_s: float = 10.0,
    ):
        super().__init__(inner, clock, default_deadline_s)
        assert direction in ("request", "response")
        self.direction = direction
        self.p = p
        self._rng = rng
        self.dropped = 0

    def _exchange(self, name, *args):
        self.calls += 1
        roll = self._rng.random() if self._rng is not None else 0.0
        if roll < self.p:
            self.dropped += 1
            self.timeouts += 1
            deadline = self._effective_deadline()
            if self.direction == "request":
                faults.fire("chaos.drop_request")
                self.clock.advance(deadline)
                raise _transport_error(
                    "injected loss: request dropped before the worker"
                )
            result = getattr(self.inner, name)(*args)
            del result  # the caller never sees it
            faults.fire("chaos.drop_response")
            self.clock.advance(deadline)
            raise _transport_error(
                "injected loss: response dropped after the exchange "
                "landed"
            )
        return getattr(self.inner, name)(*args)


def flapping_schedule(
    delay_s: float, period_s: float, duty: float = 0.5
) -> Callable[[float], float]:
    """Schedule for LatencyTransport: limp for ``duty`` of every
    ``period_s`` window, healthy otherwise — the oscillating worker
    that probation's flap detection must refuse to trust."""

    def _sched(now: float) -> float:
        phase = (now % period_s) / period_s
        return delay_s if phase < duty else 0.0

    return _sched
