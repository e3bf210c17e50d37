"""The bulk drains (pipelined, serial and megaloop rounds) against the
plain reference of flavor fungibility on seeded random deployments at a
small size (``fungibility_cases``), and on one cohort of
``chip_smoke.plain_deployment``'s 8-flavor shapes: every pass must admit
what the reference admits."""

import pytest

from fungibility_cases import (
    SEEDS,
    Reference,
    assert_equal,
    compare,
    deployment,
    one_cohort,
    replay,
    run_passes,
    runtime,
    workloads,
)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("path", ["on", "serial", "megaloop"])
def test_drains_match_the_reference(path, seed):
    # 3-cycle rounds: many round boundaries, so many resumed walks
    compared, traces = compare(path, seed, chunk=3)
    assert_equal(compared, 2)
    assert sum(t.resolution == "drain" for t in traces) > 2


@pytest.mark.parametrize("path", ["device", "on"])
def test_heads_past_the_candidate_limit_hold_their_cohort(path):
    """Four flavors in each of two groups make 16 candidates, past the
    kernels' 8: the gpu heads fall back to the host walk. A drain that
    decided the rest of their ClusterQueues and cohorts around them
    would take their turns; the whole cohort goes to the cycle loop."""
    body, n_groups, rng = deployment(7, shape=(4, 4))
    n_cq = len(body["clusterqueues"])
    batches = [workloads(rng, n_groups, n_cq, f"p{p}w", 1e5 * p) for p in range(2)]
    compared = replay(Reference(), run_passes(runtime(path), body, batches))
    assert_equal(compared, 2)


@pytest.mark.parametrize("path", ["on", "serial", "megaloop"])
@pytest.mark.parametrize("seed,cohort", [(2147483901, 3), (3000000019, 9)])
def test_resumed_walks_restart_at_the_first_flavor(seed, cohort, path):
    """A head a drain round cut off mid-walk resumes in the next round
    from its cursor; when it then loses its flavor at the group's last
    one, its walk restarts at the first flavor, as the host's does. With
    the round's template cut at the cursor that restart found nothing
    (seed 3000000019, cohort 9, 8-cycle rounds: 6 mismatches)."""
    body, batch = one_cohort(seed, cohort)
    compared = replay(Reference(), run_passes(runtime(path, chunk=8), body, [batch]))
    assert_equal(compared, 1)
