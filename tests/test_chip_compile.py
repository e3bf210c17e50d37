"""Ahead-of-time compiles of the drain entry points for a v5e chip.

Each test builds one drain problem at the size ``chip_smoke.py`` runs
it (the others from ``tests/drain_scopes.py``), catches the arguments
the lowering hands to the jitted kernel instead of running it on the
CPU, and compiles that call for one chip of a described ``v5e:2x2``
topology — or, for the mesh path, for all four chips with the
shardings the drain would place its inputs with: the TPU compiler
refuses here what it would refuse on the chip, at no chip time.
Nothing runs, so these say nothing about results or speed.

Only one process at a time may load libtpu, so the topology is described
inside a module fixture of this one file (never at import time): the
xdist worker that is given this file loads it, and no other does.
"""

import importlib.util
import os

import pytest

from kueue_tpu._jax import jax
from tests import drain_scopes

_SMOKE = os.path.join(os.path.dirname(__file__), os.pardir, "chip_smoke.py")
DEVICE_BYTES = 16 << 30  # one v5e chip's HBM


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def north_star(smoke):
    """A runtime holding the seeded 50k x 1,000-CQ north-star backlog,
    batch-applied from ``chip_smoke.plain_deployment``."""
    from kueue_tpu.controllers import ClusterRuntime
    from kueue_tpu.server import KueueServer

    rt = ClusterRuntime()
    applied = KueueServer(runtime=rt, auto_reconcile=False).apply_batch(
        smoke.plain_deployment(0)
    )
    assert not applied["rejected"], applied
    rt.reconcile_once()
    return rt


@pytest.fixture(scope="module")
def v5e():
    """The described ``v5e:2x2`` topology."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to a persistent cache
    # but cannot be read back without the chip: keep it out of any
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no compiler, no test
        jax.config.update("jax_enable_compilation_cache", was_enabled)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield topo
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(v5e):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(v5e.devices[0])


@pytest.fixture
def four_chips(v5e, monkeypatch):
    """A ``(wl,)`` mesh over the four described chips. ``device_put``
    cannot reach them, so it is replaced by one that returns the shapes
    with the shardings asked for: the captured launch then carries the
    drain's own placement."""
    import numpy as np
    from jax.sharding import Mesh

    def put(x, spec):
        def one(a, s):
            return jax.ShapeDtypeStruct(np.shape(a), a.dtype, sharding=s)

        try:
            return jax.tree_util.tree_map(one, x, spec)
        except (TypeError, ValueError):
            return jax.tree_util.tree_map(lambda a: one(a, spec), x)

    monkeypatch.setattr(jax, "device_put", put)
    return Mesh(np.array(v5e.devices).reshape(-1), ("wl",))


class _Captured(BaseException):
    """Raised in place of the kernel launch; a BaseException so that no
    containment on the way (the solver guard catches Exception) eats
    it."""

    def __init__(self, args, kwargs):
        super().__init__()
        self.args_, self.kwargs = args, kwargs


def _capture(monkeypatch, module, name, build):
    """Run ``build()`` with ``module.name`` replaced by a stub, and
    return (the real jitted function, positional args, static kwargs)
    of its first launch."""
    real = getattr(module, name)

    def stub(*args, **kwargs):
        raise _Captured(args, kwargs)

    monkeypatch.setattr(module, name, stub)
    with pytest.raises(_Captured) as caught:
        build()
    monkeypatch.setattr(module, name, real)
    return real, caught.value.args_, caught.value.kwargs


def _compile_for_chip(fn, args, kwargs, sharding):
    """Lower ``fn`` over shapes of ``args`` placed on ``sharding`` and
    compile it with the TPU compiler; returns (argument + temp) bytes."""

    def abstract(x):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            return jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=sharding,
                weak_type=getattr(x, "weak_type", False),
            )
        return x

    shapes = jax.tree_util.tree_map(abstract, args)
    compiled = fn.lower(*shapes, **kwargs).compile()
    mem = compiled.memory_analysis()
    return mem.argument_size_in_bytes + mem.temp_size_in_bytes


def _compile_placed(fn, args, kwargs, n_devices):
    """Compile a launch whose arguments already carry their shardings
    over ``n_devices`` chips; returns (argument + temp) bytes per chip."""
    spans = {
        len(a.sharding.device_set)
        for a in jax.tree_util.tree_leaves(args)
        if isinstance(a, jax.ShapeDtypeStruct)
    }
    assert spans == {n_devices}, spans
    mem = fn.lower(*args, **kwargs).compile().memory_analysis()
    return mem.argument_size_in_bytes + mem.temp_size_in_bytes


def _assert_fits(nbytes):
    assert 0 < nbytes < DEVICE_BYTES, f"{nbytes} bytes do not fit one v5e"


@pytest.fixture(scope="module")
def contended(smoke):
    """A runtime holding the contended 1,000-CQ reclaim scope of
    ``chip_smoke.populate_contended``."""
    from kueue_tpu.controllers import ClusterRuntime

    rt = ClusterRuntime()
    smoke.populate_contended(rt, 1)
    rt.reconcile_once()
    return rt


def _backlog(rt):
    """(snapshot, pending, flavors) of ``rt``'s backlog as the bulk
    path hands it to a drain."""
    from kueue_tpu.core.snapshot import take_snapshot

    snapshot = take_snapshot(rt.cache)
    return snapshot, rt.drain_backlog(snapshot), rt.cache.flavors


def _preempt_launch(rt, mesh=None):
    """The contended reclaim drain (classic preemption), as a server
    on a running clock launches it."""
    from kueue_tpu.core.drain import run_drain_preempt
    from kueue_tpu.core.queue_manager import queue_order_timestamp

    snapshot, pending, flavors = _backlog(rt)
    run_drain_preempt(
        snapshot, pending, flavors,
        timestamp_fn=lambda wl: queue_order_timestamp(
            wl, rt.queues._ts_policy
        ),
        search_width=64, mesh=mesh, ordered_stamps=True,
    )


def test_plain_drain_compiles(smoke, north_star, one_chip, monkeypatch):
    """The 50k x 1,000-CQ headline drain (``solve_drain_packed_jit``)."""
    from kueue_tpu.core.drain import run_drain
    from kueue_tpu.ops import drain_kernel

    snapshot, pending, flavors = _backlog(north_star)
    fn, args, kw = _capture(
        monkeypatch, drain_kernel, "solve_drain_packed_jit",
        lambda: run_drain(snapshot, pending, flavors, max_cells=3),
    )
    assert args[2].cells.shape[0] >= smoke.N_CQ  # one queue row per CQ
    _assert_fits(_compile_for_chip(fn, args, kw, one_chip))


def test_preempt_drain_compiles(contended, one_chip, monkeypatch):
    """The contended 1,000-CQ reclaim drain (classic preemption), as a
    server on a running clock launches it."""
    from kueue_tpu.ops import drain_kernel

    fn, args, kw = _capture(
        monkeypatch, drain_kernel, "solve_drain_preempt_packed_jit",
        lambda: _preempt_launch(contended),
    )
    assert kw["ordered_stamps"]
    _assert_fits(_compile_for_chip(fn, args, kw, one_chip))


def test_megaloop_compiles(north_star, one_chip, monkeypatch):
    """The fused K-round drain over the north-star backlog."""
    from kueue_tpu.core.drain import launch_drain_megaloop
    from kueue_tpu.ops import megaloop_kernel

    snapshot, pending, flavors = _backlog(north_star)
    fn, args, kw = _capture(
        monkeypatch, megaloop_kernel, "solve_drain_megaloop_packed_jit",
        lambda: launch_drain_megaloop(
            snapshot, pending, flavors, chunk_cycles=4, max_rounds=16,
        ),
    )
    _assert_fits(_compile_for_chip(fn, args, kw, one_chip))


def _mesh_launch(rt, monkeypatch, mesh, kind):
    """Capture the launch of one drain kind over ``rt``'s backlog,
    placed over ``mesh``."""
    from kueue_tpu.core.drain import launch_drain_megaloop, run_drain
    from kueue_tpu.ops import drain_kernel, megaloop_kernel
    from kueue_tpu.parallel import harness

    if kind == "preempt":
        # the narrow-panel canary would run on the mesh; compile the
        # exact width alone
        monkeypatch.setattr(harness, "mesh_safe_widths", lambda m, w: w[-1:])
        return _capture(
            monkeypatch, drain_kernel, "solve_drain_preempt_packed_jit",
            lambda: _preempt_launch(rt, mesh),
        )
    snapshot, pending, flavors = _backlog(rt)
    if kind == "plain":
        return _capture(
            monkeypatch, drain_kernel, "solve_drain_packed_jit",
            lambda: run_drain(snapshot, pending, flavors, mesh=mesh),
        )
    return _capture(
        monkeypatch, megaloop_kernel, "solve_drain_megaloop_packed_jit",
        lambda: launch_drain_megaloop(
            snapshot, pending, flavors, chunk_cycles=4, max_rounds=16,
            mesh=mesh,
        ),
    )


@pytest.mark.parametrize("kind", ["plain", "megaloop", "preempt"])
def test_mesh_drain_compiles(
    north_star, contended, four_chips, monkeypatch, kind
):
    """The ``--mesh auto`` drains sharded over the four chips of a
    v5e:2x2 host — where the TPU compiler once refused the plain
    drain's packed int64 output."""
    rt = contended if kind == "preempt" else north_star
    fn, args, kw = _mesh_launch(rt, monkeypatch, four_chips, kind)
    _assert_fits(_compile_placed(fn, args, kw, four_chips.size))


def _fair(problem):
    from kueue_tpu.core.drain import run_drain

    run_drain(
        problem.snapshot, problem.pending, problem.flavors,
        timestamp_fn=problem.timestamp_fn, fair_sharing=True,
    )


def _fair_preempt(problem):
    from kueue_tpu.core.drain import run_drain_fair_preempt

    run_drain_fair_preempt(
        problem.snapshot, problem.pending, problem.flavors,
        timestamp_fn=problem.timestamp_fn,
    )


def _tas(problem):
    from kueue_tpu.core.drain import run_drain_tas

    run_drain_tas(
        problem.snapshot, problem.pending, problem.flavors, problem.tas,
        timestamp_fn=problem.timestamp_fn,
    )


@pytest.mark.parametrize(
    "kernel, build, launch",
    [
        ("solve_drain_fair_packed_jit", drain_scopes.fair_problem, _fair),
        (
            "solve_drain_fair_preempt_packed_jit",
            drain_scopes.fair_preempt_problem,
            _fair_preempt,
        ),
        ("solve_drain_tas_packed_jit", drain_scopes.tas_problem, _tas),
    ],
    ids=["fair", "fair_preempt", "tas"],
)
def test_scope_drain_compiles(one_chip, monkeypatch, kernel, build, launch):
    """The fair, fair-preempt and TAS drains at their ``drain_scopes``
    sizes."""
    from kueue_tpu.ops import drain_kernel

    problem = build()
    fn, args, kw = _capture(
        monkeypatch, drain_kernel, kernel, lambda: launch(problem)
    )
    _assert_fits(_compile_for_chip(fn, args, kw, one_chip))


def test_solve_cycle_compiles(one_chip):
    """The interactive cycle kernel, as ``__graft_entry__.entry()``
    hands it to the driver."""
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))
    try:
        from __graft_entry__ import entry
    finally:
        sys.path.pop(0)
    fn, args = entry()
    _assert_fits(_compile_for_chip(jax.jit(fn), args, {}, one_chip))
