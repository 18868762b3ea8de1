"""Partial-reduce tests (reference: tests/test_ps_preduce.py — matchmaking
via the PS scheduler + group allreduce; here the reduce is a masked-mean
psum over the dp mesh axis)."""

import threading

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from hetu_tpu.ps import (PReduceScheduler, PartialReduce, partner_mask,
                         masked_mean_allreduce)


def _join_all(sched, ranks, key=0, target=-1, wait_time=50.0):
    results = {}

    def work(r):
        results[r] = sched.get_partner(key, r, target, wait_time)

    threads = [threading.Thread(target=work, args=(r,)) for r in ranks]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return results


def test_full_group_when_all_arrive():
    sched = PReduceScheduler(4)
    res = _join_all(sched, range(4), target=4)
    for r in range(4):
        assert res[r] == (0, 1, 2, 3)
    sched.close()


def test_timeout_yields_partial_group():
    sched = PReduceScheduler(4)
    # only 2 of 4 show up; short wait -> group of exactly those 2
    res = _join_all(sched, [1, 3], target=4, wait_time=30.0)
    assert res[1] == res[3] == (1, 3)
    sched.close()


def test_successive_rounds_reuse_key():
    sched = PReduceScheduler(4)
    first = _join_all(sched, range(4), target=4)
    second = _join_all(sched, [0, 2], target=2)
    assert first[0] == (0, 1, 2, 3)
    assert second[0] == second[2] == (0, 2)
    sched.close()


def test_max_worker_returns_immediately():
    sched = PReduceScheduler(8)
    # target=1: every worker forms its own group with no waiting
    res = _join_all(sched, [5], target=1, wait_time=1e6)
    assert res[5] == (5,)
    sched.close()


def test_masked_mean_allreduce_mesh():
    devs = np.array(jax.devices()[:8])
    mesh = Mesh(devs, ("dp",))
    x = jnp.arange(8, dtype=jnp.float32).reshape(8, 1)  # shard i holds [i]
    partner = (0, 2, 5)
    mask = jnp.asarray(partner_mask(partner, 8))

    def body(xs, mask):
        return masked_mean_allreduce(xs, mask, "dp")

    fn = shard_map(body, mesh=mesh, in_specs=(P("dp"), P()),
                   out_specs=P("dp"))
    out = np.asarray(jax.jit(fn)(x, mask)).reshape(-1)
    expect = np.mean([0.0, 2.0, 5.0])
    # every member (and non-member) sees the members' mean
    np.testing.assert_allclose(out[list(partner)], expect, rtol=1e-6)

    # changing the group does NOT recompile (mask is data): same jitted fn
    partner2 = (1, 6)
    mask2 = jnp.asarray(partner_mask(partner2, 8))
    out2 = np.asarray(jax.jit(fn)(x, mask2)).reshape(-1)
    np.testing.assert_allclose(out2[list(partner2)], np.mean([1.0, 6.0]),
                               rtol=1e-6)


def test_round_mask_agreement_single_canonical_group():
    """Two disjoint groups in one round -> ONE canonical mask everywhere.

    Regression for the concurrent-group mixing bug: without agreement,
    each group executed the full-axis psum with its own mask, so every
    rank's grads entered the sum while each group divided by only its
    own count."""
    sched = PReduceScheduler(4)
    pr = PartialReduce(4, scheduler=sched)
    results = {}

    def work(r, delay):
        import time as _t
        _t.sleep(delay)
        results[r] = pr.get_round_mask(r, max_worker=2, wait_time=40.0)

    # ranks 0,1 arrive together (group A); 2,3 arrive later (group B)
    threads = [threading.Thread(target=work, args=(r, d))
               for r, d in [(0, 0.0), (1, 0.0), (2, 0.15), (3, 0.15)]]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    masks = {r: tuple(results[r][0].tolist()) for r in range(4)}
    groups = {r: results[r][1] for r in range(4)}
    members = {r: results[r][2] for r in range(4)}
    # every rank got the SAME canonical mask: the group containing rank 0
    assert len(set(masks.values())) == 1
    assert all(g == (0, 1) for g in groups.values())
    assert members[0] and members[1]
    assert not members[2] and not members[3]
    sched.close()


def test_masked_mean_denominator_matches_contributors():
    """Even with per-rank masks that DISAGREE, numerator and denominator
    count the same set (psum of membership bits), so the result is the
    well-defined mean over self-declared members — not one group's sum
    over another group's count."""
    devs = np.array(jax.devices()[:4])
    mesh = Mesh(devs, ("dp",))
    x = jnp.asarray([[10.0], [20.0], [30.0], [40.0]])
    # rank i's own-mask-bit: ranks 0,1 in group A; 2,3 in group B — the
    # buggy scenario. Per-rank mask differs, but each rank's bit is 1.
    mask_a = jnp.asarray(partner_mask((0, 1), 4))
    mask_b = jnp.asarray(partner_mask((2, 3), 4))
    per_rank_mask = jnp.stack([mask_a, mask_a, mask_b, mask_b])

    def body(xs, masks):
        return masked_mean_allreduce(xs, masks[0], "dp")

    fn = shard_map(body, mesh=mesh, in_specs=(P("dp"), P("dp")),
                   out_specs=P("dp"))
    out = np.asarray(jax.jit(fn)(x, per_rank_mask)).reshape(-1)
    # all four own-bits are 1 -> union mean of all contributors (25.0),
    # NOT sum(100)/count(2)=50 as the old mixed-denominator bug gave
    np.testing.assert_allclose(out, 25.0, rtol=1e-6)


def test_partial_reduce_end_to_end():
    """Matchmake 3 of 4 workers, then reduce their grads on the mesh."""
    sched = PReduceScheduler(4)
    res = _join_all(sched, [0, 1, 3], target=4, wait_time=30.0)
    partner = res[0]
    assert partner == (0, 1, 3)
    pr = PartialReduce(4, scheduler=sched)

    devs = np.array(jax.devices()[:4])
    mesh = Mesh(devs, ("dp",))
    grads = jnp.asarray([[1.0], [2.0], [3.0], [4.0]])
    mask = jnp.asarray(partner_mask(partner, 4))

    def body(g, mask):
        return masked_mean_allreduce(g, mask, "dp")

    fn = shard_map(body, mesh=mesh, in_specs=(P("dp"), P()),
                   out_specs=P("dp"))
    out = np.asarray(jax.jit(fn)(grads, mask)).reshape(-1)
    np.testing.assert_allclose(out[list(partner)],
                               np.mean([1.0, 2.0, 4.0]), rtol=1e-6)
    sched.close()
