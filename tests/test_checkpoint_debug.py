"""Sharded checkpoint (orbax) + replica-consistency debug utilities."""

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map
import pytest

import hetu_tpu as ht
from hetu_tpu.graph.checkpoint import save_sharded, load_sharded
from hetu_tpu.parallel import debug
from hetu_tpu.parallel import make_mesh, DataParallel


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def _toy_executor(rng, tag):
    x = ht.placeholder_op(f"ck_x_{tag}", (16, 8))
    y = ht.placeholder_op(f"ck_y_{tag}", (16, 1))
    w = ht.Variable(f"ck_w_{tag}", shape=(8, 1),
                    initializer=ht.init.xavier_normal())
    loss = ht.mse_loss_op(ht.matmul_op(x, w), y)
    ex = ht.Executor({"train": [loss,
                                ht.AdamOptimizer(0.05).minimize(loss)]})
    X = rng.standard_normal((16, 8)).astype(np.float32)
    Y = rng.standard_normal((16, 1)).astype(np.float32)
    return ex, {x: X, y: Y}, f"ck_w_{tag}"


def test_sharded_checkpoint_roundtrip(rng, tmp_path):
    ex, feed, wname = _toy_executor(rng, "a")
    for _ in range(3):
        ex.run("train", feed_dict=feed)
    path = tmp_path / "ckpt"
    save_sharded(ex, path)

    # run 3 more steps, record losses, restore, replay: must match exactly
    after = [float(ex.run("train", feed_dict=feed,
                          convert_to_numpy_ret_vals=True)[0])
             for _ in range(3)]
    load_sharded(ex, path)
    replay = [float(ex.run("train", feed_dict=feed,
                           convert_to_numpy_ret_vals=True)[0])
              for _ in range(3)]
    np.testing.assert_allclose(replay, after, rtol=0, atol=0)


def test_sharded_checkpoint_restores_placement(rng, tmp_path):
    """Restore must land values back in their DP (replicated) sharding."""
    x = ht.placeholder_op("ckdp_x", (16, 8))
    y = ht.placeholder_op("ckdp_y", (16, 1))
    w = ht.Variable("ckdp_w", shape=(8, 1),
                    initializer=ht.init.xavier_normal())
    loss = ht.mse_loss_op(ht.matmul_op(x, w), y)
    ex = ht.Executor({"train": [loss,
                                ht.SGDOptimizer(0.1).minimize(loss)]},
                     dist_strategy=DataParallel(ndev=8))
    feed = {x: rng.standard_normal((16, 8)).astype(np.float32),
            y: rng.standard_normal((16, 1)).astype(np.float32)}
    ex.run("train", feed_dict=feed)
    path = tmp_path / "ckpt_dp"
    save_sharded(ex, path)
    before = np.asarray(ex.params["ckdp_w"])
    load_sharded(ex, path)
    np.testing.assert_allclose(np.asarray(ex.params["ckdp_w"]), before)
    ex.run("train", feed_dict=feed)   # still runs sharded


def test_replica_divergence_detects_desync():
    mesh = make_mesh({"dp": 8})
    from jax.sharding import NamedSharding
    good = jax.device_put(jnp.ones((4, 4)), NamedSharding(mesh, P()))
    assert debug.replica_divergence(good) == 0.0

    # build an intentionally diverged "replicated" array
    arrs = [jnp.ones((4, 4)) + (0.5 if i == 3 else 0.0) for i in range(8)]
    bad = jax.make_array_from_single_device_arrays(
        (4, 4), NamedSharding(mesh, P()),
        [jax.device_put(a, d) for a, d in zip(arrs, mesh.devices.flat)])
    assert debug.replica_divergence(bad) >= 0.5


def test_check_params_replicated(rng):
    ex, feed, wname = _toy_executor(rng, "b")
    ex.run("train", feed_dict=feed)
    assert debug.check_params_replicated(ex) == {}


def test_equal_across_canary():
    mesh = make_mesh({"dp": 8})
    same = jnp.ones((8, 4))
    diff = same.at[3].add(2.0)

    f = shard_map(lambda v: debug.equal_across(v, "dp")[None],
                  mesh=mesh, in_specs=P("dp"), out_specs=P("dp"))
    assert float(np.max(np.asarray(jax.jit(f)(same)))) == 0.0
    assert float(np.max(np.asarray(jax.jit(f)(diff)))) > 1.0


def test_fingerprint_stable(rng):
    tree = {"a": jnp.asarray(rng.standard_normal((4, 4)), jnp.float32),
            "b": [jnp.ones((2,))]}
    f1 = debug.fingerprint(tree)
    f2 = debug.fingerprint(jax.tree_util.tree_map(jnp.asarray, tree))
    assert f1 == f2
    tree["a"] = tree["a"] + 1.0
    assert debug.fingerprint(tree) != f1


def test_checkpoint_carries_conv_layout_tag(rng):
    """ADVICE r4: state_dict embeds a machine-checkable conv-layout tag;
    loading an untagged checkpoint with 4-D params warns, and a non-HWIO
    tag is rejected with a pointer at the converter."""
    import warnings as _warnings
    import hetu_tpu as ht
    from hetu_tpu.layers import Conv2d
    x = ht.placeholder_op("clt_x", (2, 3, 8, 8))
    conv = Conv2d(3, 3, kernel_size=3, padding=1)   # 3->3 3x3: all-equal
    s = ht.reduce_sum_op(ht.reduce_sum_op(ht.reduce_sum_op(
        ht.reduce_sum_op(conv(x), axes=3), axes=2), axes=1), axes=0)
    ex = ht.Executor({"eval": [s]}, training=False)
    state = ex.state_dict()
    assert state["format"]["conv_layout"] == "HWIO"

    # tagged checkpoint loads silently
    with _warnings.catch_warnings():
        _warnings.simplefilter("error")
        ex.load_state_dict(state)

    # untagged (pre-r5) checkpoint with a 4-D param warns
    legacy = dict(state)
    legacy.pop("format")
    with pytest.warns(UserWarning, match="conv-layout tag"):
        ex.load_state_dict(legacy)

    # declared OIHW is refused with the converter named
    bad = dict(state)
    bad["format"] = {"conv_layout": "OIHW"}
    with pytest.raises(ValueError, match="load_oihw"):
        ex.load_state_dict(bad)
