"""Multi-head latent attention, the training half (DeepSeek-V2,
arXiv:2405.04434; the softmax layers of Ling-3.0 and Xing4.0): keys and values
come out of one low-rank latent, a head's query and key are a part without
position and a rotary part, and the scores are wider than the values.

    [q_h^nope (d_n) | q_h^rope (d_r)] = (x W_q)_h          full-rank query, or
        c_q = RMSNorm_rq(x W_qa);  (c_q W_qb)_h            ``q_lora_rank``
    [c (r) | k^rope (d_r)] = x W_kva ;  c = RMSNorm_r(c)
    [k_h^nope (d_n) | v_h (d_v)] = (c W_kvb)_h
    q_h = RMSNorm(q_h),  k_h = RMSNorm([k_h^nope | k^rope])  over d_n + d_r
                     (``qk_norm``: learned, one weight for all heads), then
                     rotary on the last d_r of both (HF's half-split form)
    o_h = softmax_causal(q_h k_h^T / sqrt(d_n + d_r)) v_h
    y = [ o_h * sigmoid(x w_gate)_h ] W_o                  one gate a head

``rope_scaling`` is ``ops/rotary.py yarn_scaling``'s tuple over the ``d_r``
rotary dimensions; ``softmax_scale_mult`` multiplies the scores' scale
(DeepSeek-V3's ``mscale``: where ``mscale == mscale_all_dim`` the tables carry
a factor of 1 and the scale ``(0.1 mscale_all_dim ln factor + 1)^2``).  A
layer built without ``q_lora_rank``, ``rope_scaling`` and
``softmax_scale_mult`` builds the graph it built before they existed.

The core product is the one fused-attention op (``ops/attention.py``).  What
stands between the projections and it is ONE node (``_Heads``), which chooses
by what it can see when it is traced (``dispatch.take("mla_pack", ..)``, counted
in ``hetu_attn_layout_total``).  Heads of 128 + 64 over values of whole lane
tiles, on a TPU with no mesh: the kernel pairs of ``ops/pallas/mla_pack.py``
lay q and k out at a stride of 256 lanes a head on ``[B, S, H x 256]`` (norm
and rotation in that pass, 64 exact zeros behind a head's 192) and cut the
values out of ``c W_kvb``; the flash kernels read all three in place (``bshd``
with two head sizes, entry ``bshd_v128``) and write the context ``[B, S, H
d_v]``, which the gate a head and ``W_o`` read as it lies: no view by heads
and no transpose of anything.  Everywhere else (the CPU, a mesh, other head
sizes): ``_queries`` / ``_keys`` / ``_values`` on the views by heads and the
flash kernels (or the ``jax.numpy`` composition) over ``[B, H, S, d_n + d_r]``
queries and keys and ``[B, H, S, d_v]`` values, no operand padded to the
other's width.  What a serving cache would hold (the latent ``c`` and
``k^rope``, and the absorbed decode that reads them) is not here (ROADMAP
Queue 2, M7).
"""

from __future__ import annotations

from .attention import count_layout, gate_heads_in_place
from .base import BaseLayer, fresh_name, project
from .. import initializers as init
from ..graph.node import VariableOp, scope
from ..ops.attention import scaled_dot_product_attention_op
from ..ops.base import ScopedOp as _Scoped
from ..ops.pallas import dispatch, mla_pack as kernels
from ..ops.rotary import pair_item_op

_SCOPE = "hetu_attn"


def _rms(x, w, eps):
    import jax
    import jax.numpy as jnp
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (xf * w.astype(jnp.float32)).astype(x.dtype)


def _rope_last(x, d_rope, theta, scaling=None):
    """Rotary on the last ``d_rope`` of ``x [B, S, H, d]``."""
    import jax.numpy as jnp
    from ..ops.rotary import _rotary
    keep = x.shape[-1] - d_rope
    more = {} if scaling is None else {"scaling": scaling}
    return jnp.concatenate(
        [x[..., :keep],
         _rotary(x[..., keep:], theta=theta, seq_axis=1, **more)], -1)


def _latent(kva, w_norm, *, rank, eps):
    """The normed latent ``c [B, S, r]`` out of ``x W_kva``."""
    return _rms(kva[..., :rank], w_norm, eps)


def _queries(q, *w_norm, heads, d_rope, theta, eps, scaling=None):
    """``x W_q [B, S, H (d_n + d_r)]`` -> ``[B, H, S, d_n + d_r]``; ``w_norm``:
    the norm's weight where the layer has one."""
    B, S, _ = q.shape
    q = q.reshape(B, S, heads, -1)
    if w_norm:
        q = _rms(q, w_norm[0], eps)
    return _rope_last(q, d_rope, theta, scaling).transpose(0, 2, 1, 3)


def _keys(kvb, kva, *w_norm, heads, d_nope, d_rope, rank, theta, eps,
          scaling=None):
    """A head's key: its own part of ``c W_kvb`` beside the one rotary part
    all heads share: ``[B, H, S, d_n + d_r]``."""
    import jax.numpy as jnp
    B, S, _ = kvb.shape
    nope = kvb.reshape(B, S, heads, -1)[..., :d_nope]
    rope = jnp.broadcast_to(kva[..., None, rank:], (B, S, heads, d_rope))
    k = jnp.concatenate([nope, rope], -1)
    if w_norm:
        k = _rms(k, w_norm[0], eps)
    return _rope_last(k, d_rope, theta, scaling).transpose(0, 2, 1, 3)


def _values(kvb, *, heads, d_nope):
    B, S, _ = kvb.shape
    return kvb.reshape(B, S, heads, -1)[..., d_nope:].transpose(0, 2, 1, 3)


def _by_heads(q, kvb, kva, *w_norm, d_nope, rank, **rot):
    """``(q, k, v)`` on the views by heads, ``[B, H, S, .]``."""
    return (_queries(q, *w_norm[:1], **rot),
            _keys(kvb, kva, *w_norm[1:], d_nope=d_nope, rank=rank, **rot),
            _values(kvb, heads=rot["heads"], d_nope=d_nope))


def _in_place(q, kvb, kva, *w_norm, heads, d_nope, d_rope, rank, theta, eps,
              scaling=None):
    """``(q^ [B, S, H x 256], k^ [B, S, H x 256], v [B, S, H d_v])`` through
    the kernel pairs; the tables are a rotation of the first ``d_rope`` lanes
    of one lane tile (``ops/pallas/rotary.py``'s three-table form)."""
    from ..ops.rotary import _pair_tables
    more = {} if scaling is None else {"scaling": scaling}
    tables = kernels.tables(_pair_tables(
        seq_len=q.shape[1], dim=kernels.LANES, theta=theta, rotary_dim=d_rope,
        **more))
    wq, wk = w_norm or (None, None)
    k, v = kernels.keys_values(kvb, kva[..., rank:], tables, wk, heads, eps)
    return kernels.queries(q, tables, wq, heads, eps), k, v


class _Heads(_Scoped):
    """``(x W_q, c W_kvb, x W_kva) -> (q, k, v)``, with the two norms' weights
    ``(x W_q, w_q, c W_kvb, x W_kva, w_k)`` (the order in which the three
    nodes this one stands for read them): at a stride of 256 lanes a head
    through the kernels where ``dispatch.take`` says so (the one thing the
    functions cannot see, a mesh, is the node's), else on the views by
    heads."""

    def _compute(self, input_vals, ctx):
        if len(input_vals) == 5:
            q, wq, kvb, kva, wk = input_vals
            input_vals = (q, kvb, kva, wq, wk)
        q, kvb = input_vals[:2]
        a = self.attrs
        why = kernels.unsupported(
            q, heads=a["heads"], d_nope=a["d_nope"], d_rope=a["d_rope"],
            d_v=kvb.shape[-1] // a["heads"] - a["d_nope"])
        if dispatch.take("mla_pack", ctx.mesh, why):
            count_layout("bshd", "latent_in_place")
            return _in_place(*input_vals, **a)
        count_layout("bhsd", "latent_" + (why or (
            "no_mosaic" if ctx.mesh is None else "under_a_mesh")))
        return _by_heads(*input_vals, **a)


def _out(ctx_, w_out, *gate):
    """The context times ``W_o``, each head first times the sigmoid of its
    one gate number (where the layer has a gate): ``[B, S, H d_v]`` as the
    in-place kernels leave it, or ``[B, H, S, d_v]`` -> ``[B, S, H d_v]``."""
    import jax
    import jax.numpy as jnp
    if ctx_.ndim == 3:
        return (gate_heads_in_place(ctx_, gate[0]) if gate else ctx_) @ w_out
    o = ctx_.transpose(0, 2, 1, 3)
    if gate:
        o = (o.astype(jnp.float32)
             * jax.nn.sigmoid(gate[0].astype(jnp.float32))[..., None]
             ).astype(ctx_.dtype)
    return o.reshape(o.shape[:2] + (-1,)) @ w_out


class LatentAttention(BaseLayer):
    def __init__(self, hidden_size, num_heads, kv_lora_rank,
                 qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
                 rope_theta=10000.0, qk_norm=True, head_gate=True, eps=1e-6,
                 q_lora_rank=None, rope_scaling=None, softmax_scale_mult=None,
                 name=None):
        name = fresh_name(name or "mla")
        self.num_heads = num_heads
        self.rope_scaling = rope_scaling
        self.scale = (qk_nope_head_dim + qk_rope_head_dim) ** -0.5
        if softmax_scale_mult is not None:
            self.scale *= softmax_scale_mult
        self.d_nope, self.d_rope, self.d_v = (qk_nope_head_dim,
                                              qk_rope_head_dim, v_head_dim)
        self.rank, self.theta, self.eps = kv_lora_rank, rope_theta, eps
        d_qk = qk_nope_head_dim + qk_rope_head_dim
        self.d_qk = d_qk

        def var(n, shape, how=None):
            return VariableOp(f"{name}_{n}", shape,
                              how or init.xavier_normal())
        #: the low-rank query path: ``W_qa``, the norm's weight, then
        #: ``q_proj`` is ``W_qb``
        self.qa_proj = self.qa_norm = None
        if q_lora_rank:
            self.qa_proj = var("qa_weight", (hidden_size, q_lora_rank))
            self.qa_norm = var("qa_norm_scale", (q_lora_rank,), init.ones())
        self.q_proj = var("q_weight",
                          (q_lora_rank or hidden_size, num_heads * d_qk))
        self.kva_proj = var("kva_weight",
                            (hidden_size, kv_lora_rank + qk_rope_head_dim))
        self.kv_norm = var("kv_norm_scale", (kv_lora_rank,), init.ones())
        self.kvb_proj = var("kvb_weight",
                            (kv_lora_rank,
                             num_heads * (qk_nope_head_dim + v_head_dim)))
        self.q_norm = self.k_norm = None
        if qk_norm:
            self.q_norm = var("q_norm_scale", (d_qk,), init.ones())
            self.k_norm = var("k_norm_scale", (d_qk,), init.ones())
        self.gate_proj = (var("gate_weight", (hidden_size, num_heads))
                          if head_gate else None)
        self.out_proj = var("out_weight",
                            (num_heads * v_head_dim, hidden_size))

    def __call__(self, x):
        S = lambda fn, *a, **kw: _Scoped(fn, _SCOPE, *a, **kw)
        rot = dict(heads=self.num_heads, d_rope=self.d_rope,
                   theta=self.theta, eps=self.eps)
        if self.rope_scaling is not None:
            rot["scaling"] = self.rope_scaling
        kva = S(project, x, self.kva_proj)
        c = S(_latent, kva, self.kv_norm, rank=self.rank, eps=self.eps)
        kvb = S(project, c, self.kvb_proj)
        normed = self.q_norm is not None
        xq = x if self.qa_proj is None else S(
            _rms, S(project, x, self.qa_proj), self.qa_norm, eps=self.eps)
        heads = _Heads(None, _SCOPE, S(project, xq, self.q_proj),
                       *([self.q_norm] if normed else []), kvb, kva,
                       *([self.k_norm] if normed else []),
                       d_nope=self.d_nope, rank=self.rank, **rot)
        with scope(_SCOPE):
            q, k, v = (pair_item_op(heads, index=i) for i in range(3))
            # ``num_heads`` is read where q, k and v come flat
            ctx_ = scaled_dot_product_attention_op(
                q, k, v, causal=True, scale=self.scale,
                num_heads=self.num_heads)
        gate = ([] if self.gate_proj is None
                else [S(project, x, self.gate_proj)])
        return S(_out, ctx_, self.out_proj, *gate)
