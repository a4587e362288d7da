"""The ordered sampling walk in slot space (``engine/core.py
sample_visited_at``) against the formulation it replaced, and the shape
of the traced program.

Until PR 43 the walk by a visit-order operand carried the feasibility
mask INTO visit order (one gather through the argsort of the order),
ran ``sample_visited`` there and carried both masks BACK (two more
gathers): three permutations of one boolean row a slot, 33 us each at
N = 4,096 on the v5e (PERF.md section 6, PR 42).  That formulation is
kept here as the oracle.  The structural tests fail when someone brings
a permutation of the masks back into the pod loop.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ksim_tpu.engine.core import sample_visited, sample_visited_at

I32_MAX = int(np.iinfo(np.int32).max)


def by_three_gathers(feasible, real, pos, start, n_real, k):
    """The walk as it was: masks into visit order, the prefix count
    there, masks back to slot order."""
    n = feasible.shape[0]
    by_pos = jnp.argsort(pos).astype(jnp.int32)
    pos_at = jnp.minimum(pos, n - 1)
    in_order = jnp.arange(n, dtype=jnp.int32) < n_real
    vis_o, sam_o, nxt = sample_visited(
        feasible[by_pos] & in_order, in_order, start, n_real, k
    )
    return vis_o[pos_at] & real, sam_o[pos_at] & feasible, nxt


def make_case(n, n_real, seed):
    """Live slots interleaved with dead ones, a random visit order over
    the live ones (dense 0 .. n_real - 1, big elsewhere: what
    ``NodeTree.positions`` lays down), ~60 % of them feasible, and one
    ``feasible`` bit on a dead slot where there is one."""
    rng = np.random.default_rng(seed)
    real = np.zeros(n, bool)
    real[rng.choice(n, size=n_real, replace=False)] = True
    pos = np.full(n, I32_MAX, np.int32)
    pos[real] = rng.permutation(n_real).astype(np.int32)
    feasible = real & (rng.random(n) < 0.6)
    if n_real < n:
        feasible[np.flatnonzero(~real)[0]] = True  # a bit on a dead slot
    return feasible, real, pos


def _ks(n_feasible):
    return {
        "zero": 0,
        "one": 1,
        "third": max(n_feasible // 3, 1),
        "exact": n_feasible,
        "one_more": n_feasible + 1,
        "unsampled": I32_MAX,
    }


def _starts(n_real):
    return {"zero": 0, "inside": n_real // 2 + 1, "beyond": n_real + 7}


_both = jax.jit(lambda *a: (sample_visited_at(*a), by_three_gathers(*a)))


@pytest.mark.parametrize("start_name", ["zero", "inside", "beyond"])
@pytest.mark.parametrize(
    "k_name", ["zero", "one", "third", "exact", "one_more", "unsampled"]
)
@pytest.mark.parametrize("n_real_name", ["none", "one", "half", "all"])
@pytest.mark.parametrize("n", [64, 4096])
def test_equals_the_three_gather_walk(n, n_real_name, k_name, start_name):
    n_real = {"none": 0, "one": 1, "half": n // 2, "all": n}[n_real_name]
    seed = sum(map(ord, n_real_name + k_name + start_name)) + n
    feasible, real, pos = make_case(n, n_real, seed)
    k = _ks(int((feasible & real).sum()))[k_name]
    start = _starts(n_real)[start_name]
    args = (
        jnp.asarray(feasible),
        jnp.asarray(real),
        jnp.asarray(pos),
        jnp.int32(start),
        jnp.int32(n_real),
        jnp.int32(k),
    )
    (visited, sample, nxt), (visited0, sample0, nxt0) = _both(*args)
    np.testing.assert_array_equal(np.asarray(visited), np.asarray(visited0))
    np.testing.assert_array_equal(np.asarray(sample), np.asarray(sample0))
    assert int(nxt) == int(nxt0)
    # What the contract says, beside the oracle: the sample is the
    # feasible part of the visited real slots, k bounds it, and the
    # start index moves by the nodes visited.
    visited, sample = np.asarray(visited), np.asarray(sample)
    assert not (visited & ~real).any()
    np.testing.assert_array_equal(sample, visited & feasible)
    assert sample.sum() == min(max(k, 0), int((feasible & real).sum()))
    assert int(nxt) == (start % max(n_real, 1) + visited.sum()) % max(n_real, 1)


def test_visits_in_the_given_order_not_in_slot_order():
    """A hand case: slots 0..5, slot 3 dead; visit order 4, 0, 5, 2, 1.
    From start 3 (slot 2) with k = 2: slot 2 is infeasible, 1 and 4 are
    the two found, so three are visited and the next start is 1."""
    real = np.array([1, 1, 1, 0, 1, 1], bool)
    pos = np.array([1, 4, 3, I32_MAX, 0, 2], np.int32)
    feasible = np.array([1, 1, 0, 1, 1, 0], bool)
    visited, sample, nxt = sample_visited_at(
        jnp.asarray(feasible), jnp.asarray(real), jnp.asarray(pos),
        jnp.int32(3), jnp.int32(5), jnp.int32(2),
    )
    assert np.asarray(visited).tolist() == [0, 1, 1, 0, 1, 0]
    assert np.asarray(sample).tolist() == [0, 1, 0, 0, 1, 0]
    assert int(nxt) == 1


# -- the traced program ------------------------------------------------------


def _walk(jaxpr):
    """Every equation of a jaxpr, sub-jaxprs included, with the number
    of loop bodies (``while`` / ``scan``) around it."""

    def go(jp, depth):
        for eqn in jp.eqns:
            yield eqn, depth
            inner = depth + (eqn.primitive.name in ("while", "scan"))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from go(sub, inner)

    yield from go(jaxpr, 0)


def node_axis_permutations(jaxpr, n, min_depth=0):
    """(gathers of a whole node-axis row, sorts of the node axis) at
    ``min_depth`` loops deep or deeper.  A gather counts when its
    operand is one row of ``n`` and it draws ``n`` elements from it: a
    permutation of the row, not a scalar read."""
    gathers = sorts = 0
    for eqn, depth in _walk(jaxpr):
        if depth < min_depth:
            continue
        name = eqn.primitive.name
        if name == "gather":
            operand, out = eqn.invars[0].aval, eqn.outvars[0].aval
            if operand.shape == (n,) and int(np.prod(out.shape)) >= n:
                gathers += 1
        elif name == "sort":
            if any(v.aval.shape[-1:] == (n,) for v in eqn.invars):
                sorts += 1
    return gathers, sorts


def _abstract_args(n):
    return (
        jax.ShapeDtypeStruct((n,), jnp.bool_),
        jax.ShapeDtypeStruct((n,), jnp.bool_),
        jax.ShapeDtypeStruct((n,), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32),
    )


def test_the_function_permutes_no_mask_and_sorts_once():
    n = 256
    jp = jax.make_jaxpr(sample_visited_at)(*_abstract_args(n)).jaxpr
    assert node_axis_permutations(jp, n) == (0, 1)
    # The control: the check sees the old formulation's three gathers.
    old = jax.make_jaxpr(by_three_gathers)(*_abstract_args(n)).jaxpr
    assert node_axis_permutations(old, n) == (3, 1)
    # And the slot-order walk sorts and permutes nothing.
    plain = jax.make_jaxpr(sample_visited)(
        *(a for i, a in enumerate(_abstract_args(n)) if i != 2)
    ).jaxpr
    assert node_axis_permutations(plain, n) == (0, 0)


@pytest.fixture(scope="module")
def walk_tensor_plan():
    """A lowered window of the benchmark's churn stream under sampling
    (200 nodes, node replacements in every step: every attempt goes by
    the walk tensor), as ``tests/test_churn_default_config.py`` runs it."""
    from ksim_tpu.scenario import ScenarioRunner
    from ksim_tpu.scenario.spec import operations_from_spec
    from ksim_tpu.state.cluster import ClusterStore

    mp = pytest.MonkeyPatch()
    mp.syspath_prepend(
        os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
    )
    from kinds import churn

    ops = churn.churn_operations(0, n_nodes=200, n_events=500, ops_per_step=100)
    mp.undo()
    runner = ScenarioRunner(
        store=ClusterStore(), preemption=True, node_sampling=True,
        max_pods_per_pass=1024, pod_bucket_min=128, device_replay=True,
    )
    runner.run(iter(operations_from_spec({"operations": ops})))
    driver = runner.replay_driver
    assert driver.fallback_steps == 0 and not driver.unsupported
    stats = driver.stats()
    assert stats["sampled_by_rank"] == stats["sampled_attempts"] > 0
    return driver._last_plan


def _segment_jaxpr(plan):
    from ksim_tpu.engine import replay

    const, (ev, st) = replay._pack_plan_buffers(plan, (plan.ev, plan.state0))
    return jax.make_jaxpr(
        lambda c, e, s: replay._segment_body(plan.statics, plan.prog, c, e, s)
    )(const, ev, st).jaxpr


def test_the_pod_loop_of_a_walk_tensor_window_permutes_no_node_row(
    walk_tensor_plan, monkeypatch
):
    """The segment program of a ``sample`` 2 window: a scan over the
    steps, in it the pod loop (a ``while`` over blocks of slots, in it
    the slots' scan).  Two loops deep and deeper (= inside the pod
    loop) no whole node-axis row is gathered and the slot's body holds
    ONE sort of the node axis; with the three-gather walk put back, the
    same count sees its three gathers."""
    from ksim_tpu.engine import core

    plan = walk_tensor_plan
    assert plan.statics.sample == 2
    n = plan.ev["walk"].shape[-1]
    assert node_axis_permutations(_segment_jaxpr(plan), n, min_depth=2) == (0, 1)
    monkeypatch.setattr(core, "sample_visited_at", by_three_gathers)
    assert node_axis_permutations(_segment_jaxpr(plan), n, min_depth=2) == (3, 1)
