"""``hetu_tpu.dataloader.block_diffusion_noise``: the noising step of block
diffusion's data path.  Shapes and types, the clean half untouched, one level
a block, determinism by the generator's state, the masked share within its
binomial band, labels and weights, and the counters."""

import numpy as np
import pytest

from hetu_tpu import telemetry
from hetu_tpu.dataloader import block_diffusion_noise

MASK = 999


def draw(seed, B=4, L=256, block=4, eps=1e-3, dtype=np.int64):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, MASK, (B, L)).astype(dtype)
    return ids, block_diffusion_noise(ids, block, MASK, rng, eps)


@pytest.mark.parametrize("block", [1, 4, 32])
@pytest.mark.parametrize("dtype", [np.int32, np.int64])
def test_shapes_types_and_the_clean_half(block, dtype):
    ids, (x, labels, weights) = draw(0, block=block, dtype=dtype)
    B, L = ids.shape
    assert x.shape == (B, 2 * L) and x.dtype == dtype
    assert labels.shape == (B, L) and labels.dtype == dtype
    assert weights.shape == (B, L) and weights.dtype == np.float32
    np.testing.assert_array_equal(x[:, :L], ids)
    noised, masked = x[:, L:], labels >= 0
    # a masked position holds the mask token and is labelled with its token;
    # every other position is as it was and has no label
    assert (noised[masked] == MASK).all() and (noised[~masked]
                                               == ids[~masked]).all()
    np.testing.assert_array_equal(labels[masked], ids[masked])
    assert (labels[~masked] == -1).all()


@pytest.mark.parametrize("block", [4, 32])
def test_one_level_a_block_and_its_weight(block):
    eps = 0.25
    _, (_, _, weights) = draw(1, block=block, eps=eps)
    by_block = weights.reshape(weights.shape[0], -1, block)
    assert (by_block == by_block[..., :1]).all()       # one level a block
    t = 1.0 / by_block[..., 0]
    assert (t >= eps - 1e-6).all() and (t <= 1.0 + 1e-6).all()
    assert len(np.unique(t)) == t.size                 # a level of its own


def test_the_same_state_gives_the_same_batch_and_another_another():
    _, a = draw(7)
    _, b = draw(7)
    _, c = draw(8)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert any((x != y).any() for x, y in zip(a, c))


@pytest.mark.parametrize("seed", range(4))
def test_the_masked_share_lies_in_its_binomial_band(seed):
    """A token is masked with probability ``t`` of its block, ``t ~ U[eps,
    1]``: the share over 64 x 1,024 tokens is within five deviations of
    ``(1 + eps) / 2`` (the levels' draw, a block of 4 alike, dominates: the
    variance of a block's count is ``4 E[t (1 - t)] + 16 Var[t]``)."""
    eps, block, n = 1e-3, 4, 64 * 1024
    _, (_, labels, weights) = draw(seed, B=64, L=1024, block=block, eps=eps)
    share = (labels >= 0).mean()
    var_block = block * (1 / 6) + block * block * (1 / 12)
    deviation = np.sqrt(var_block * (n / block)) / n
    assert abs(share - (1 + eps) / 2) < 5 * deviation
    # and a position's chance is its own level: the masked share of the
    # blocks whose level is under a half against those over it
    low = weights > 2.0
    assert (labels >= 0)[low].mean() < 0.3 < 0.7 < (labels >= 0)[~low].mean()


def test_the_counters_count_what_was_masked_and_kept():
    telemetry.enable()
    try:
        def read():
            metric = telemetry.get_registry().snapshot().get(
                "hetu_diffusion_positions_total", {"samples": []})
            return {s["labels"]["state"]: s["value"]
                    for s in metric["samples"]}
        before = read()
        _, (_, labels, _) = draw(3)
        after = read()
        masked = int((labels >= 0).sum())
        assert after["masked"] - before.get("masked", 0) == masked
        assert after["kept"] - before.get("kept", 0) == labels.size - masked
    finally:
        telemetry.shutdown()


def test_a_sequence_that_is_not_whole_blocks_is_refused():
    rng = np.random.default_rng(0)
    with pytest.raises(AssertionError):
        block_diffusion_noise(np.zeros((1, 10), np.int64), 4, MASK, rng)
