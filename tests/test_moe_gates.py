"""Gate-variant tests (reference: examples/moe/test_moe_{top,hash,ktop1,
sam,base}.py run under mpirun; here on the jnp gating functions + graph)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import hetu_tpu as ht
from hetu_tpu.ops.moe import (ktop1_gating, sam_gating,
                              base_balance_gating, balance_assignment)
from hetu_tpu.layers.moe import MoELayer


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def test_ktop1_gating_prototypes(rng):
    T, E, k, C = 16, 8, 2, 8
    logits = jnp.asarray(rng.standard_normal((T, E)), jnp.float32)
    dispatch, combine, aux = ktop1_gating(logits, k, C)
    assert dispatch.shape == (T, E, C)
    # each token gets exactly one slot in EACH prototype half
    per_token = np.asarray(dispatch.sum((1, 2)))
    np.testing.assert_allclose(per_token, 2.0)
    first_half = np.asarray(dispatch[:, :E // 2].sum((1, 2)))
    np.testing.assert_allclose(first_half, 1.0)
    assert float(aux) > 0


def test_sam_gating_group_locality(rng):
    T, E, G, k, C = 16, 8, 2, 2, 16
    logits = jnp.asarray(rng.standard_normal((T, E)), jnp.float32)
    dispatch, combine, aux = sam_gating(logits, k, C, G)
    d = np.asarray(dispatch)
    # all of a token's experts live in ONE group
    for t in range(T):
        used = np.nonzero(d[t].sum(-1))[0]
        assert len(used) == k
        assert len({int(e) // (E // G) for e in used}) == 1
    assert np.isfinite(float(aux))


def test_sam_gating_no_slot_collision():
    """Token A's top-1 and token B's top-2 on the same expert must occupy
    DIFFERENT capacity slots (regression: shared per-expert queues)."""
    logits = jnp.asarray([[5.0, 4.0, -9.0, -9.0],
                          [4.0, 5.0, -9.0, -9.0]], jnp.float32)
    dispatch, combine, _ = sam_gating(logits, k=2, capacity=4, num_groups=1)
    # each (expert, slot) pair holds at most one token
    per_slot = np.asarray(dispatch.sum(0))
    assert per_slot.max() <= 1.0, per_slot
    # and all 4 assignments survived
    assert float(dispatch.sum()) == 4.0


def test_sam_gating_rejects_k_exceeding_group():
    logits = jnp.zeros((4, 8), jnp.float32)
    with pytest.raises(AssertionError, match="exhaust"):
        sam_gating(logits, k=3, capacity=8, num_groups=4)


def test_balance_assignment_is_balanced(rng):
    T, E = 32, 4
    scores = jnp.asarray(rng.standard_normal((T, E)), jnp.float32)
    idx = np.asarray(balance_assignment(scores))
    counts = np.bincount(idx, minlength=E)
    assert counts.max() <= (T + E - 1) // E     # capacity respected


def test_base_balance_gating(rng):
    T, E, C = 16, 4, 4
    scores = jnp.asarray(rng.standard_normal((T, E)), jnp.float32)
    dispatch, combine, aux = base_balance_gating(scores, C)
    per_expert = np.asarray(dispatch.sum((0, 2)))
    assert per_expert.max() <= C
    # every token dispatched exactly once (capacity T/E*C is enough here)
    np.testing.assert_allclose(np.asarray(dispatch.sum((1, 2))), 1.0)
    assert float(aux) == 0.0


def test_aux_only_matches_full_gating(rng):
    """The O(T·E) aux-only paths must equal the aux returned by the full
    gating (the MoEAuxLossOp uses them to avoid recomputing the [T,E,C]
    dispatch/combine tensors in a separate subexecutor)."""
    import jax.numpy as jnp
    from hetu_tpu.ops.moe import (top_k_gating, ktop1_gating, sam_gating,
                                  top_k_balance_aux, ktop1_balance_aux,
                                  sam_balance_aux)
    logits = jnp.asarray(rng.standard_normal((64, 8)), jnp.float32)
    _, _, aux = top_k_gating(logits, 2, 16)
    np.testing.assert_allclose(float(top_k_balance_aux(logits)), float(aux),
                               rtol=1e-6)
    _, _, aux = ktop1_gating(logits, 2, 16)
    np.testing.assert_allclose(float(ktop1_balance_aux(logits, 2)),
                               float(aux), rtol=1e-6)
    _, _, aux = sam_gating(logits, 2, 16, 2)
    np.testing.assert_allclose(float(sam_balance_aux(logits, 2)),
                               float(aux), rtol=1e-6)


@pytest.mark.parametrize("gate,kw", [
    ("ktop1", {}), ("sam", {"num_groups": 2}), ("balance", {})])
def test_moe_layer_trains_with_gate(gate, kw, rng):
    B, S, Hd, E = 4, 8, 16, 4
    x = ht.placeholder_op(f"moe_{gate}_x", (B, S, Hd))
    y = ht.placeholder_op(f"moe_{gate}_y", (B, S, Hd))
    moe = MoELayer(Hd, 2 * Hd, E, k=2 if gate != "balance" else 1,
                   gate=gate, **kw)
    out = moe(x)
    loss = ht.mse_loss_op(out, y) + 0.01 * moe.aux_loss()
    ex = ht.Executor({"train": [loss,
                                ht.AdamOptimizer(0.01).minimize(loss)]})
    X = rng.standard_normal((B, S, Hd)).astype(np.float32)
    Y = (0.5 * X).astype(np.float32)
    losses = [float(ex.run("train", feed_dict={x: X, y: Y},
                           convert_to_numpy_ret_vals=True)[0])
              for _ in range(12)]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], (gate, losses)


@pytest.mark.parametrize("gate_kind", ["ktop1", "sam"])
def test_sparse_path_matches_dense_for_ktop1_and_sam(rng, gate_kind):
    """KTop1/SAM gates also expose the CHOICES form: the sparse
    scatter-dispatch MoELayer matches a twin on the dense einsums (the same
    gate without its choices form) end to end."""
    from conftest import dense_twin_gate
    from hetu_tpu.layers import MoELayer
    from hetu_tpu.layers.moe import KTop1Gate, SAMGate

    B, S, H = 4, 8, 16
    X = rng.standard_normal((B, S, H)).astype(np.float32)
    Y = np.zeros_like(X)
    losses, prev = {}, None
    for mode in ("sparse", "dense"):
        name = f"ks_{gate_kind}_{mode}"
        gate = (SAMGate(H, 4, 2, name=name) if gate_kind == "sam"
                else KTop1Gate(H, 4, name=name))
        moe = MoELayer(H, 32, num_experts=4, k=2, capacity_factor=2.0,
                       gate=gate if mode == "sparse"
                       else dense_twin_gate(gate), name=name)
        x = ht.placeholder_op(f"ksx_{gate_kind}_{mode}", X.shape)
        y = ht.placeholder_op(f"ksy_{gate_kind}_{mode}", X.shape)
        loss = ht.mse_loss_op(moe(x), y) + 0.01 * moe.aux_loss()
        ex = ht.Executor({"train": [loss, ht.AdamOptimizer(0.01)
                                    .minimize(loss)]}, seed=4)
        from conftest import clone_params_into
        prev = clone_params_into(ex, prev)
        losses[mode] = [
            float(ex.run("train", feed_dict={x: X, y: Y},
                         convert_to_numpy_ret_vals=True)[0])
            for _ in range(3)]
    np.testing.assert_allclose(losses["sparse"], losses["dense"],
                               rtol=2e-5, atol=2e-6)
