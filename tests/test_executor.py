"""Executor + optimizer integration (reference: tests/test_optimizer.py,
mnist_mlp convergence pattern)."""

import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu.optim import lr_scheduler


def _toy_problem(seed=0):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((64, 10)).astype(np.float32)
    true_w = rng.standard_normal((10, 1)).astype(np.float32)
    Y = X @ true_w + 0.01 * rng.standard_normal((64, 1)).astype(np.float32)
    return X, Y


@pytest.mark.parametrize("opt_cls,kwargs", [
    (ht.SGDOptimizer, dict(learning_rate=0.1)),
    (ht.MomentumOptimizer, dict(learning_rate=0.05)),
    (ht.MomentumOptimizer, dict(learning_rate=0.05, nesterov=True)),
    (ht.AdaGradOptimizer, dict(learning_rate=0.5)),
    (ht.AdamOptimizer, dict(learning_rate=0.1)),
    (ht.AdamWOptimizer, dict(learning_rate=0.1, weight_decay=0.001)),
    (ht.AMSGradOptimizer, dict(learning_rate=0.1)),
    (ht.LambOptimizer, dict(learning_rate=0.1)),
])
def test_optimizer_converges(opt_cls, kwargs):
    X, Y = _toy_problem()
    x = ht.placeholder_op("x", X.shape)
    y_ = ht.placeholder_op("y", Y.shape)
    w = ht.Variable("w", initializer=ht.init.zeros(), shape=(10, 1))
    pred = ht.matmul_op(x, w)
    loss = ht.reduce_mean_op(ht.reduce_sum_op(
        ht.pow_op(pred - y_, exponent=2.0), axes=1))
    opt = opt_cls(**kwargs)
    train_op = opt.minimize(loss)
    ex = ht.Executor([loss, train_op])
    first = None
    for i in range(200):
        lv, _ = ex.run(feed_dict={x: X, y_: Y},
                       convert_to_numpy_ret_vals=True)
        if first is None:
            first = lv
    assert lv < first * 0.05, f"{opt_cls.__name__} failed: {first} -> {lv}"


class TestSparseOptimizer:
    """Lazy (IndexedSlices) in-graph embedding updates — reference
    optimizer.py sparse op pairs + src/ops/OptimizersSparse.cu."""

    V, D, B, F = 64, 8, 16, 4

    class _FixedInit:
        def __init__(self, vals):
            self.vals = vals

        def __call__(self, key, shape, dtype=None):
            import jax.numpy as jnp
            return jnp.asarray(self.vals, dtype or jnp.float32)

    def _graph(self, opt, sparse, tag="", strategy=None):
        rng = np.random.default_rng(0)
        init_vals = np.random.default_rng(42).standard_normal(
            (self.V, self.D)).astype(np.float32)
        ids = ht.placeholder_op(f"so_ids{tag}", (self.B, self.F),
                                dtype=np.int32)
        y = ht.placeholder_op(f"so_y{tag}", (self.B, self.F, self.D))
        table = ht.Variable(f"so_table{tag}", shape=(self.V, self.D),
                            initializer=self._FixedInit(init_vals))
        e = ht.embedding_lookup_op(table, ids)
        loss = ht.reduce_mean_op(ht.pow_op(e - y, exponent=2.0))
        train = opt.minimize(loss,
                             sparse_vars=[table] if sparse else ())
        ex = ht.Executor([loss, train], seed=7, dist_strategy=strategy)
        feeds = [{ids: rng.integers(0, self.V, (self.B, self.F)),
                  y: rng.standard_normal(
                      (self.B, self.F, self.D)).astype(np.float32)}
                 for _ in range(4)]
        return ex, table, feeds

    def test_sgd_sparse_matches_dense_exactly(self):
        # SGD has no cross-step slot dynamics: lazy == dense bitwise-ish
        runs = []
        for sparse in (False, True):
            ex, table, feeds = self._graph(ht.SGDOptimizer(0.1), sparse,
                                           tag=f"_{int(sparse)}")
            for f in feeds:
                ex.run(feed_dict=f)
            runs.append(np.asarray(ex.params[table.name]))
        np.testing.assert_allclose(runs[0], runs[1], rtol=1e-6, atol=1e-6)

    def test_adam_sparse_is_lazy(self):
        # untouched rows keep their moments frozen (lazy semantics);
        # touched rows converge the loss like dense
        ex, table, feeds = self._graph(ht.AdamOptimizer(0.05), True)
        p0 = np.asarray(ex.params[table.name])
        losses = [float(ex.run(feed_dict=f,
                               convert_to_numpy_ret_vals=True)[0])
                  for f in feeds * 4]
        assert losses[-1] < losses[0]
        p1 = np.asarray(ex.params[table.name])
        touched = np.unique(np.concatenate(
            [np.asarray(f[list(f)[0]]).ravel() for f in feeds]))
        untouched = np.setdiff1d(np.arange(self.V), touched)
        if untouched.size:                    # pure-lazy: never written
            np.testing.assert_array_equal(p0[untouched], p1[untouched])
        assert not np.allclose(p0[touched], p1[touched])

    def test_clip_norm_counts_sparse_grads(self):
        # the global-norm clip sees the deduped sparse rows: with a tiny
        # clip bound, updates shrink vs unclipped.  SGD — Adam's update is
        # scale-invariant (the clip would only show through eps)
        deltas = []
        for clip in (None, 1e-3):
            opt = ht.SGDOptimizer(0.05)
            ids = ht.placeholder_op(f"cl_ids_{clip}", (8,),
                                    dtype=np.int32)
            y = ht.placeholder_op(f"cl_y_{clip}", (8, self.D))
            table = ht.Variable(f"cl_table_{clip}", shape=(32, self.D),
                                initializer=ht.init.normal(0.0, 1.0))
            e = ht.embedding_lookup_op(table, ids)
            loss = ht.reduce_mean_op(ht.pow_op(e - y, exponent=2.0))
            grads_op = opt.minimize(loss, sparse_vars=[table])
            grads_op.clip_global_norm = clip
            ex = ht.Executor([loss, grads_op], seed=3)
            p0 = np.asarray(ex.params[table.name])
            rng = np.random.default_rng(1)
            ex.run(feed_dict={ids: rng.integers(0, 32, (8,)),
                              y: rng.standard_normal((8, self.D))
                              .astype(np.float32)})
            deltas.append(
                np.abs(np.asarray(ex.params[table.name]) - p0).max())
        assert deltas[1] < deltas[0]

    def test_sparse_matches_single_device_under_dp(self):
        """Lazy updates are exact under GSPMD dp sharding (the deduped
        (ids, rows) path composes with batch-sharded lookup grads)."""
        import jax
        if len(jax.devices()) < 8:
            pytest.skip("needs 8 virtual devices")
        from hetu_tpu.parallel import DataParallel
        res = []
        for tag, strat in (("ref", None), ("dp", DataParallel(ndev=8))):
            ex, table, feeds = self._graph(ht.SGDOptimizer(0.1), True,
                                           tag=f"_dp{tag}", strategy=strat)
            for f in feeds:
                ex.run(feed_dict=f)
            res.append(np.asarray(ex.params[table.name]))
        np.testing.assert_allclose(res[0], res[1], atol=1e-5)

    def test_sparse_under_mixed_precision(self):
        """Lazy updates hit the f32 master copy under bf16 compute, like
        the dense path (slots and masters stay full precision)."""
        import jax.numpy as jnp
        rng = np.random.default_rng(0)
        init_vals = np.random.default_rng(42).standard_normal(
            (self.V, self.D)).astype(np.float32)
        ids = ht.placeholder_op("mp_ids", (self.B, self.F),
                                dtype=np.int32)
        y = ht.placeholder_op("mp_y", (self.B, self.F, self.D))
        t = ht.Variable("mp_table", shape=(self.V, self.D),
                        initializer=self._FixedInit(init_vals))
        e = ht.embedding_lookup_op(t, ids)
        loss = ht.reduce_mean_op(ht.pow_op(e - y, exponent=2.0))
        train = ht.AdamOptimizer(0.05).minimize(loss, sparse_vars=[t])
        ex_mp = ht.Executor([loss, train], seed=7,
                            compute_dtype=jnp.bfloat16)
        losses = []
        for _ in range(4):
            fm = {ids: rng.integers(0, self.V, (self.B, self.F)),
                  y: rng.standard_normal(
                      (self.B, self.F, self.D)).astype(np.float32)}
            losses.append(float(ex_mp.run(
                feed_dict=fm, convert_to_numpy_ret_vals=True)[0]))
        assert np.isfinite(losses).all() and losses[-1] < losses[0]
        # master copy stays f32
        assert np.asarray(ex_mp.params[t.name]).dtype == np.float32

    def test_sparse_state_checkpoints(self, tmp_path):
        """Adam moments of a lazily-updated table ride save/load: loss
        sequences replay exactly after restore."""
        ex, table, feeds = self._graph(ht.AdamOptimizer(0.05), True,
                                       tag="_ck")
        for f in feeds[:2]:
            ex.run(feed_dict=f)
        p = str(tmp_path / "sparse.ckpt")
        ex.save(p)
        a = [float(ex.run(feed_dict=f,
                          convert_to_numpy_ret_vals=True)[0])
             for f in feeds]
        ex.load(p)
        b = [float(ex.run(feed_dict=f,
                          convert_to_numpy_ret_vals=True)[0])
             for f in feeds]
        assert a == b

    def test_pipeline_refuses_sparse(self):
        import jax
        if len(jax.devices()) < 2:
            pytest.skip("needs multi-device mesh")
        from hetu_tpu.parallel import make_mesh
        ids = ht.placeholder_op("pr_ids", (4, 2), dtype=np.int32)
        y = ht.placeholder_op("pr_y", (4, 2, self.D))
        table = ht.Variable("pr_table", shape=(16, self.D),
                            initializer=ht.init.normal(0.0, 1.0))
        e = ht.embedding_lookup_op(table, ids)
        loss = ht.reduce_mean_op(ht.pow_op(e - y, exponent=2.0))
        op = ht.SGDOptimizer(0.1).minimize(loss, sparse_vars=[table])
        with pytest.raises(NotImplementedError, match="sparse"):
            ht.Executor({"train": [loss, op]},
                        mesh=make_mesh({"pp": 2}), pipeline="gpipe",
                        num_micro=2)

    def test_lamb_refuses_sparse(self):
        ids = ht.placeholder_op("lb_ids", (4,), dtype=np.int32)
        table = ht.Variable("lb_table", shape=(16, 4),
                            initializer=ht.init.normal(0.0, 1.0))
        loss = ht.reduce_mean_op(ht.embedding_lookup_op(table, ids))
        with pytest.raises(ValueError, match="whole-tensor"):
            ht.LambOptimizer(0.1).minimize(loss, sparse_vars=[table])

    def test_non_lookup_use_falls_back_to_dense(self):
        ids = ht.placeholder_op("fb_ids", (4,), dtype=np.int32)
        table = ht.Variable("fb_table", shape=(16, 4),
                            initializer=ht.init.normal(0.0, 1.0))
        loss = ht.reduce_mean_op(ht.embedding_lookup_op(table, ids)) \
            + ht.reduce_mean_op(table)        # second, non-lookup use
        op = ht.SGDOptimizer(0.1).minimize(loss, sparse_vars=[table])
        assert table in op.var_list and not op.sparse


def test_optimizer_matches_torch_sgd_momentum():
    import torch
    X, Y = _toy_problem(1)
    Wv = np.zeros((10, 1), np.float32)
    x = ht.placeholder_op("x", X.shape)
    y_ = ht.placeholder_op("y", Y.shape)
    w = ht.Variable("w", value=Wv.copy())
    loss = ht.reduce_mean_op(ht.reduce_sum_op(
        ht.pow_op(ht.matmul_op(x, w) - y_, exponent=2.0), axes=1))
    train_op = ht.MomentumOptimizer(learning_rate=0.01,
                                    momentum=0.9).minimize(loss)
    ex = ht.Executor([loss, train_op])

    tw = torch.from_numpy(Wv.copy()).requires_grad_()
    topt = torch.optim.SGD([tw], lr=0.01, momentum=0.9)
    tx, ty = torch.from_numpy(X), torch.from_numpy(Y)
    for _ in range(10):
        ex.run(feed_dict={x: X, y_: Y})
        topt.zero_grad()
        tloss = ((tx @ tw - ty) ** 2).sum(1).mean()
        tloss.backward()
        topt.step()
    np.testing.assert_allclose(np.asarray(ex.params[w.name]),
                               tw.detach().numpy(), rtol=1e-4, atol=1e-5)


def test_adam_matches_torch():
    import torch
    X, Y = _toy_problem(2)
    Wv = np.zeros((10, 1), np.float32)
    x = ht.placeholder_op("x", X.shape)
    y_ = ht.placeholder_op("y", Y.shape)
    w = ht.Variable("w", value=Wv.copy())
    loss = ht.reduce_mean_op(ht.reduce_sum_op(
        ht.pow_op(ht.matmul_op(x, w) - y_, exponent=2.0), axes=1))
    train_op = ht.AdamOptimizer(learning_rate=0.01, beta1=0.9, beta2=0.999,
                                eps=1e-8).minimize(loss)
    ex = ht.Executor([loss, train_op])
    tw = torch.from_numpy(Wv.copy()).requires_grad_()
    topt = torch.optim.Adam([tw], lr=0.01, betas=(0.9, 0.999), eps=1e-8)
    tx, ty = torch.from_numpy(X), torch.from_numpy(Y)
    for _ in range(10):
        ex.run(feed_dict={x: X, y_: Y})
        topt.zero_grad()
        tloss = ((tx @ tw - ty) ** 2).sum(1).mean()
        tloss.backward()
        topt.step()
    np.testing.assert_allclose(np.asarray(ex.params[w.name]),
                               tw.detach().numpy(), rtol=1e-3, atol=1e-5)


def test_named_subgraphs_train_validate():
    X, Y = _toy_problem(3)
    x = ht.placeholder_op("x", X.shape)
    y_ = ht.placeholder_op("y", Y.shape)
    w = ht.Variable("w", initializer=ht.init.zeros(), shape=(10, 1))
    loss = ht.reduce_mean_op(ht.reduce_sum_op(
        ht.pow_op(ht.matmul_op(x, w) - y_, exponent=2.0), axes=1))
    train_op = ht.SGDOptimizer(learning_rate=0.1).minimize(loss)
    ex = ht.Executor({"train": [loss, train_op], "validate": [loss]})
    l0 = ex.run("validate", feed_dict={x: X, y_: Y},
                convert_to_numpy_ret_vals=True)[0]
    for _ in range(50):
        ex.run("train", feed_dict={x: X, y_: Y})
    l1 = ex.run("validate", feed_dict={x: X, y_: Y},
                convert_to_numpy_ret_vals=True)[0]
    assert l1 < l0 * 0.1
    # validate must not mutate params
    p_before = np.asarray(ex.params[w.name])
    ex.run("validate", feed_dict={x: X, y_: Y})
    np.testing.assert_array_equal(p_before, np.asarray(ex.params[w.name]))


def test_checkpoint_save_load(tmp_path):
    X, Y = _toy_problem(4)
    x = ht.placeholder_op("x", X.shape)
    y_ = ht.placeholder_op("y", Y.shape)
    w = ht.Variable("w", initializer=ht.init.xavier_normal(), shape=(10, 1))
    loss = ht.reduce_mean_op(ht.reduce_sum_op(
        ht.pow_op(ht.matmul_op(x, w) - y_, exponent=2.0), axes=1))
    train_op = ht.AdamOptimizer(learning_rate=0.05).minimize(loss)
    ex = ht.Executor([loss, train_op])
    for _ in range(5):
        ex.run(feed_dict={x: X, y_: Y})
    path = tmp_path / "ckpt.pkl"
    ex.save(str(path))
    run1 = [ex.run(feed_dict={x: X, y_: Y},
                   convert_to_numpy_ret_vals=True)[0] for _ in range(5)]

    ex.load(str(path))
    run2 = [ex.run(feed_dict={x: X, y_: Y},
                   convert_to_numpy_ret_vals=True)[0] for _ in range(5)]
    np.testing.assert_allclose(run1, run2, rtol=1e-6)


def test_lr_scheduler_steps():
    X, Y = _toy_problem(5)
    x = ht.placeholder_op("x", X.shape)
    y_ = ht.placeholder_op("y", Y.shape)
    w = ht.Variable("w", initializer=ht.init.zeros(), shape=(10, 1))
    loss = ht.reduce_mean_op(ht.reduce_sum_op(
        ht.pow_op(ht.matmul_op(x, w) - y_, exponent=2.0), axes=1))
    sched = lr_scheduler.StepScheduler(0.1, step_size=10, gamma=0.5)
    train_op = ht.SGDOptimizer(learning_rate=sched).minimize(loss)
    ex = ht.Executor([loss, train_op])
    for _ in range(30):
        ex.run(feed_dict={x: X, y_: Y})
    import jax.numpy as jnp
    assert int(ex.opt_state[train_op.name]["step"]) == 30


def test_batchnorm_state_updates():
    rng = np.random.default_rng(6)
    X = rng.standard_normal((8, 3, 4, 4)).astype(np.float32) * 2 + 1
    x = ht.placeholder_op("x", X.shape)
    scale = ht.Variable("bn_scale", value=np.ones(3, np.float32))
    bias = ht.Variable("bn_bias", value=np.zeros(3, np.float32))
    y = ht.batch_normalization_op(x, scale, bias)
    loss = ht.reduce_mean_op(y)
    train_op = ht.SGDOptimizer(learning_rate=0.0).minimize(loss)
    ex = ht.Executor({"train": [y, train_op], "validate": [y]})
    out_train = ex.run("train", feed_dict={x: X},
                       convert_to_numpy_ret_vals=True)[0]
    # training output is batch-normalized
    np.testing.assert_allclose(out_train.mean(axis=(0, 2, 3)), 0.0, atol=1e-5)
    rm = np.asarray(ex.params[y.running_mean.name])
    assert np.abs(rm).sum() > 0  # running stats moved
    np.testing.assert_allclose(rm, 0.1 * X.mean(axis=(0, 2, 3)), rtol=1e-4)


def test_batchnorm_precise_stats_survives_huge_mean():
    """precise_stats=True keeps the f32 variance exact when
    |mean| >> std — the case where one-pass E[d^2]-E[d]^2 with the
    (zero-initialized) running-mean shift cancels catastrophically."""
    rng = np.random.default_rng(7)
    base = rng.standard_normal((8, 3, 4, 4)).astype(np.float32)
    X = base + 1e4  # per-channel mean ~1e4, std ~1
    outs = {}
    for precise in (False, True):
        with ht.name_scope():
            x = ht.placeholder_op("pbn_x", X.shape)
            scale = ht.Variable("pbn_scale", value=np.ones(3, np.float32))
            bias = ht.Variable("pbn_bias", value=np.zeros(3, np.float32))
            y = ht.batch_normalization_op(x, scale, bias,
                                          precise_stats=precise)
            train_op = ht.SGDOptimizer(learning_rate=0.0).minimize(
                ht.reduce_mean_op(y))
            ex = ht.Executor({"train": [y, train_op]})
        outs[precise] = ex.run("train", feed_dict={x: X},
                               convert_to_numpy_ret_vals=True)[0]
        # running_var starts at ones: rv = 0.9*1 + 0.1*var after one step
        var = (np.asarray(ex.params[y.running_var.name]) - 0.9) / 0.1
        if precise:
            # exact two-pass form: variance stays correct (~1), so the
            # normalized output matches the f64 oracle
            want = (X.astype(np.float64)
                    - X.astype(np.float64).mean((0, 2, 3), keepdims=True))
            want /= np.sqrt(
                X.astype(np.float64).var((0, 2, 3), keepdims=True) + 1e-5)
            np.testing.assert_allclose(outs[True], want, atol=1e-2)
            np.testing.assert_allclose(
                var, X.astype(np.float64).var((0, 2, 3)), rtol=1e-3)
        else:
            # the fast default genuinely loses precision here (documents
            # the tradeoff this test's sibling path exists to fix)
            assert not np.allclose(
                var, X.astype(np.float64).var((0, 2, 3)), rtol=0.2)


def test_cost_analysis_reports_flops():
    X = np.random.default_rng(0).standard_normal((32, 16)).astype(np.float32)
    x = ht.placeholder_op("ca_x", X.shape)
    w = ht.Variable("ca_w", shape=(16, 8), initializer=ht.init.zeros())
    loss = ht.reduce_mean_op(ht.matmul_op(x, w))
    ex = ht.Executor({"train": [loss,
                                ht.SGDOptimizer(0.1).minimize(loss)]})
    step_before = ex._global_step
    w0 = np.asarray(ex.params["ca_w"]).copy()
    # pure analysis: works before any run, mutates nothing
    cost = ex.subexecutor["train"].cost_analysis(feed_dict={x: X})
    assert cost and float(cost.get("flops", 0)) > 0
    assert ex._global_step == step_before
    np.testing.assert_array_equal(np.asarray(ex.params["ca_w"]), w0)


def test_strategy_json_roundtrip(tmp_path):
    from hetu_tpu.parallel import DataParallel, MegatronLM, Strategy
    for s in (DataParallel(ndev=8), MegatronLM(dp=2, tp=4)):
        p = str(tmp_path / f"{type(s).__name__}.json")
        s.save_json(p)
        s2 = Strategy.load_json(p)
        assert type(s2) is type(s)
        assert dict(s2.mesh.shape) == dict(s.mesh.shape)


def test_variable_names_deterministic_across_instances():
    # VERDICT round 1 (weak #8): a second model instance must get the SAME
    # parameter names, not process-wide `_1` suffixes, so checkpoints keyed
    # by name survive construction order.
    from hetu_tpu.models import MLP

    names_a = sorted(l.weight.name for l in MLP(dims=(4, 3, 2)).linears)
    names_b = sorted(l.weight.name for l in MLP(dims=(4, 3, 2)).linears)
    assert names_a == names_b
    assert not any(n.endswith("_1") for n in names_b)


def test_executor_rejects_colliding_variable_names():
    from hetu_tpu.models import MLP
    import pytest

    x = ht.placeholder_op("nsx", (2, 4))
    m1, m2 = MLP(dims=(4, 3, 2)), MLP(dims=(4, 3, 2))
    loss = ht.reduce_mean_op(m1(x) + m2(x))
    with pytest.raises(ValueError, match="distinct variables named"):
        ht.Executor([loss])
    # distinct explicit names compose fine in one executor
    m3, m4 = MLP(dims=(4, 3, 2), name="a"), MLP(dims=(4, 3, 2), name="b")
    loss2 = ht.reduce_mean_op(m3(x) + m4(x))
    ex = ht.Executor([loss2])
    assert len(ex.params) == len(m3.linears) * 4


def test_rbg_rng_checkpoint_roundtrip(tmp_path):
    # rbg keys serialize as (4,)-uint32 key_data; load must wrap them back
    # with the SAME impl (a bare wrap_key_data assumes threefry and raises)
    x = ht.placeholder_op("rbg_x", (2, 4))
    w = ht.Variable("rbg_w", shape=(4, 3), initializer=ht.init.ones())
    loss = ht.reduce_mean_op(ht.dropout_op(ht.matmul_op(x, w), 0.9))
    ex = ht.Executor({"train": [loss, ht.SGDOptimizer(0.1).minimize(loss)]},
                     rng_impl="rbg")
    X = np.ones((2, 4), np.float32)
    ex.run("train", feed_dict={x: X})
    p = str(tmp_path / "ck.npz")
    ex.save(p)
    ex2 = ht.Executor({"train": [loss, ht.SGDOptimizer(0.1).minimize(loss)]},
                      rng_impl="rbg")
    ex2.load(p)
    a = ex.run("train", feed_dict={x: X}, convert_to_numpy_ret_vals=True)
    b = ex2.run("train", feed_dict={x: X}, convert_to_numpy_ret_vals=True)
    np.testing.assert_allclose(a[0], b[0])


def test_comm_mode_allreduce_is_data_parallel():
    # reference comm_mode='AllReduce' (executor.py:278): dense grads
    # allreduce across replicas == our DataParallel annotation
    import jax
    x = ht.placeholder_op("cm_x", (16, 8))
    y = ht.placeholder_op("cm_y", (16, 1))
    w = ht.Variable("cm_w", shape=(8, 1), initializer=ht.init.zeros())
    loss = ht.mse_loss_op(ht.matmul_op(x, w), y)
    ex = ht.Executor([loss, ht.SGDOptimizer(0.1).minimize(loss)],
                     comm_mode="AllReduce")
    assert ex.mesh is not None and len(ex.mesh.devices.flatten()) == \
        len(jax.devices())
    X = np.ones((16, 8), np.float32)
    Y = np.full((16, 1), 2.0, np.float32)
    l0 = ex.run(feed_dict={x: X, y: Y}, convert_to_numpy_ret_vals=True)[0]
    l1 = ex.run(feed_dict={x: X, y: Y}, convert_to_numpy_ret_vals=True)[0]
    assert l1 < l0

    with pytest.warns(UserWarning, match="no PSEmbedding"):
        ht.Executor([loss], comm_mode="PS")
    with pytest.raises(ValueError, match="unknown comm_mode"):
        ht.Executor([loss], comm_mode="bogus")




def test_fast_feed_cache_semantics():
    """The steady-state fast path must (a) apply in-place value swaps in
    the same feed_dict object, (b) disarm cleanly when the dict's
    structure or value classes change, (c) never skip dtype casts for
    numpy feeds."""
    import jax
    import jax.numpy as jnp
    x = ht.placeholder_op("ff_x", (4, 8))
    w = ht.Variable("ff_w", value=np.ones((8, 2), np.float32))
    out = ht.matmul_op(x, w)
    s = ht.reduce_sum_op(ht.reduce_sum_op(out, axes=1), axes=0)
    ex = ht.Executor({"eval": [s]}, training=False)
    sub = ex.subexecutor["eval"]

    a = jnp.ones((4, 8), jnp.float32)
    feed = {x: a}
    v1 = float(ex.run("eval", feed_dict=feed,
                      convert_to_numpy_ret_vals=True)[0])
    assert v1 == 64.0
    pairs, autos = sub._fast_feed
    assert [k for k, _, _ in pairs] == [x] and autos == []

    # (a) in-place swap of the value in the SAME dict object
    feed[x] = 2 * a
    v2 = float(ex.run("eval", feed_dict=feed,
                      convert_to_numpy_ret_vals=True)[0])
    assert v2 == 128.0

    # (c) numpy value: fast path must disarm and the cast still happen
    feed[x] = np.full((4, 8), 3.0, np.float64)
    v3 = float(ex.run("eval", feed_dict=feed,
                      convert_to_numpy_ret_vals=True)[0])
    assert v3 == 192.0

    # (b) a DIFFERENT dict object with the same structure stays fast —
    # the cache keys on the feed pytree structure, not dict identity
    # (a device prefetcher hands over a fresh dict every step)
    v4 = float(ex.run("eval", feed_dict={x: a},
                      convert_to_numpy_ret_vals=True)[0])
    assert v4 == 64.0
    assert sub._fast_feed is not None


def test_fast_feed_dtype_guard_disarms_and_casts():
    """ADVICE r4: a wrong-dtype DEVICE array swapped into the cached
    feed dict must not silently retrace a new program variant — the
    fast path disarms and the slow path casts it to the declared
    dtype."""
    import jax.numpy as jnp
    x = ht.placeholder_op("ffd_x", (4, 8))
    w = ht.Variable("ffd_w", value=np.ones((8, 2), np.float32))
    s = ht.reduce_sum_op(ht.reduce_sum_op(ht.matmul_op(x, w), axes=1),
                         axes=0)
    ex = ht.Executor({"eval": [s]}, training=False)
    sub = ex.subexecutor["eval"]
    feed = {x: jnp.ones((4, 8), jnp.float32)}
    assert float(ex.run("eval", feed_dict=feed,
                        convert_to_numpy_ret_vals=True)[0]) == 64.0
    assert sub._fast_feed is not None
    # swap in a bf16 device array under the SAME dict object
    feed[x] = jnp.full((4, 8), 2.0, jnp.bfloat16)
    v = float(ex.run("eval", feed_dict=feed,
                     convert_to_numpy_ret_vals=True)[0])
    assert v == 128.0
    # the guard disarmed the fast path for that call, and re-arming only
    # happens for clean declared-dtype device feeds
    feed[x] = jnp.full((4, 8), 3.0, jnp.float32)
    assert float(ex.run("eval", feed_dict=feed,
                        convert_to_numpy_ret_vals=True)[0]) == 192.0


def test_profile_returns_consistent_pair():
    """ADVICE r4: Executor.profile returns (dt, aggs_or_None) with and
    without trace_dir — no type-switching return."""
    x = ht.placeholder_op("pr_x", (2, 4))
    s = ht.reduce_sum_op(ht.reduce_sum_op(x * 2.0, axes=1), axes=0)
    ex = ht.Executor({"eval": [s]}, training=False)
    out = ex.profile("eval", feed_dict={x: np.ones((2, 4), np.float32)},
                     repeats=2)
    assert isinstance(out, tuple) and len(out) == 2
    dt, aggs = out
    assert isinstance(dt, float) and dt > 0
    assert aggs is None


# -- donation of the training state (SubExecutor._should_donate) -----------

def _donation_problem(tag, **executor_kw):
    """A small dense model with a ``train`` and a ``validate`` subgraph."""
    X, Y = _toy_problem(11)
    x = ht.placeholder_op(f"dn_x_{tag}", X.shape)
    y_ = ht.placeholder_op(f"dn_y_{tag}", Y.shape)
    w = ht.Variable(f"dn_w_{tag}", shape=(10, 1),
                    initializer=ht.init.xavier_normal())
    b = ht.Variable(f"dn_b_{tag}", shape=(1,), initializer=ht.init.zeros())
    loss = ht.reduce_mean_op(ht.reduce_sum_op(
        ht.pow_op(ht.matmul_op(x, w) + b - y_, exponent=2.0), axes=1))
    train_op = ht.AdamOptimizer(learning_rate=0.05).minimize(loss)
    ex = ht.Executor({"train": [loss, train_op], "validate": [loss]},
                     seed=3, **executor_kw)
    return ex, {x: X, y_: Y}


def _state_leaves(ex):
    import jax
    return jax.tree_util.tree_leaves((ex.params, ex.opt_state))


def _donates_gauge(subgraph):
    from hetu_tpu import telemetry
    samples = telemetry.get_registry().snapshot()[
        "hetu_executor_donates_state"]["samples"]
    return {s["labels"]["subgraph"]: s["value"]
            for s in samples}.get(subgraph)


@pytest.fixture
def tel():
    from hetu_tpu import telemetry
    telemetry.get_registry().reset()
    telemetry.enable()
    yield telemetry
    telemetry.disable()


@pytest.mark.parametrize("entry,subgraph,config,donated", [
    ("run", "train", {}, True),
    ("run_steps", "train", {}, True),
    ("run", "validate", {}, False),
    ("run", "train", {"donate_params": False}, False),
    ("run_steps", "train", {"donate_params": False}, False),
    ("run", "validate", {"donate_params": True}, False),
])
def test_training_state_donation_rule(tel, entry, subgraph, config, donated):
    """Under ``auto`` a training subgraph hands its state to the step
    program and an evaluation subgraph leaves it alive;
    ``donate_params`` overrides the rule for training subgraphs only."""
    ex, feed = _donation_problem(f"{entry}_{subgraph}_{len(config)}",
                                 **config)
    before = _state_leaves(ex)
    assert before and not any(v.is_deleted() for v in before)
    if entry == "run":
        ex.run(subgraph, feed_dict=feed)
    else:
        ex.run_steps(subgraph, feed, 3)
    assert [v.is_deleted() for v in before] == [donated] * len(before)
    assert _donates_gauge(subgraph) == int(donated)
    # the executor's own bindings are the step's outputs, never the
    # donated inputs
    assert not any(v.is_deleted() for v in _state_leaves(ex))
    out = ex.run("validate", feed_dict=feed, convert_to_numpy_ret_vals=True)
    assert np.isfinite(out[0])


def test_data_parallel_donates_every_leaf_without_warning(tel):
    """On a mesh every donated leaf must alias an output (the
    ``out_shardings`` pin in ``_build``): a leaf XLA cannot reuse makes
    jax warn "Some donated buffers were not usable" at compile time."""
    import warnings
    from hetu_tpu.parallel import DataParallel
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        ex, feed = _donation_problem("dp", dist_strategy=DataParallel(
            ndev=8))
        ex.run("train", feed_dict=feed)   # _commit_state places the state
        placed = _state_leaves(ex)
        ex.run("train", feed_dict=feed)
        ex.run_steps("train", feed, 2)
    assert not [str(w.message) for w in caught
                if "donated buffers" in str(w.message)]
    assert placed and all(v.is_deleted() for v in placed)
    assert _donates_gauge("train") == 1


def test_a_program_that_keeps_its_state_returns_what_it_changed_on_a_mesh(
        tel):
    """A program that does not take its state donated returns, under a mesh
    too, only the leaves it replaced (a second whole state does not fit
    beside a state that fills half a chip: PERF.md, PR 72), with shardings
    the compiler chose; ``_dispatch`` places them where the next call's
    ``in_shardings`` take them.  So: a program that changes nothing hands the
    very same arrays on, a training program that keeps its state alive
    replaces every leaf on the state's own shardings in one compilation, and
    the steps are the one-device program's."""
    import warnings
    import jax
    from hetu_tpu.parallel import DataParallel
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    one, feed_one = _donation_problem("keep1", donate_params=False)
    ex, feed = _donation_problem("keep4", donate_params=False,
                                 dist_strategy=DataParallel(ndev=4))
    ex.run("validate", feed_dict=feed)       # _commit_state places the state
    for name in ("dn_w", "dn_b"):
        ex.params[f"{name}_keep4"] = jax.device_put(
            np.asarray(one.params[f"{name}_keep1"]),
            ex.params[f"{name}_keep4"].sharding)
    held = _state_leaves(ex)
    ex.run("validate", feed_dict=feed)
    assert all(a is b for a, b in zip(held, _state_leaves(ex)))
    sub = ex.subexecutor["train"]
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        for _ in range(3):
            got = ex.run("train", feed_dict=feed,
                         convert_to_numpy_ret_vals=True)[0]
            want = one.run("train", feed_dict=feed_one,
                           convert_to_numpy_ret_vals=True)[0]
            assert abs(got - want) < 1e-5 * abs(want)
            assert sub._returns_changed_only and sub._state_sh is not None
            placed = jax.tree_util.tree_map(
                lambda v, sh: v.sharding == sh, (ex.params, ex.opt_state),
                sub._state_sh)
            assert placed and all(jax.tree_util.tree_leaves(placed))
        assert not any(v.is_deleted() for v in held)
        assert not any(a is b for a, b in zip(held, _state_leaves(ex)))
        # the placed leaves are what the program was compiled to take
        assert sub._jitted._cache_size() == 1
    assert not [str(w.message) for w in caught
                if "donated buffers" in str(w.message)]
    assert _donates_gauge("train") == 0


def test_state_dict_survives_later_donation():
    """``state_dict`` hands out host arrays; the device buffers they were
    read from are donated by the next step.  On the CPU ``np.asarray`` of
    a device array can be a zero-copy view, so the saved values must still
    be that step's after more steps have run, and loading them must bring
    the loss of that step back."""
    ex, feed = _donation_problem("sd")
    for _ in range(3):
        ex.run("train", feed_dict=feed)
    saved = ex.state_dict()
    frozen = {k: np.array(v, copy=True) for k, v in saved["params"].items()}
    loss_then = ex.run("validate", feed_dict=feed,
                       convert_to_numpy_ret_vals=True)[0]
    after = [ex.run("train", feed_dict=feed,
                    convert_to_numpy_ret_vals=True)[0] for _ in range(3)]
    for k, v in frozen.items():
        np.testing.assert_array_equal(v, saved["params"][k])
        assert not np.array_equal(v, np.asarray(ex.params[k]))
    ex.load_state_dict(saved)
    assert ex.run("validate", feed_dict=feed,
                  convert_to_numpy_ret_vals=True)[0] == loss_then
    again = [ex.run("train", feed_dict=feed,
                    convert_to_numpy_ret_vals=True)[0] for _ in range(3)]
    np.testing.assert_array_equal(after, again)


def test_guard_skip_keeps_donated_state_bitwise():
    """The in-graph ``skip`` select reads the old state inside the program
    that was given it as donated arguments: a NaN batch must leave params
    and optimiser state bit for bit."""
    from hetu_tpu.resilience import StepGuard
    guard = StepGuard(policy="skip", defer=False)
    ex, feed = _donation_problem("gs", step_guard=guard)
    for _ in range(3):
        ex.run("train", feed_dict=feed)
    held = _state_leaves(ex)
    before = [np.array(v, copy=True) for v in held]
    bad = {k: np.array(v, copy=True) for k, v in feed.items()}
    next(iter(bad.values()))[0, 0] = np.nan
    ex.run("train", feed_dict=bad)
    assert all(v.is_deleted() for v in held)    # donation was on
    assert guard.stats["skipped"] == 1
    for want, got in zip(before, _state_leaves(ex)):
        np.testing.assert_array_equal(want, np.asarray(got))
