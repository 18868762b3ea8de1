"""Rotary position embeddings (RoPE), ALiBi biases, and GQA head repeat.

The reference's Llama family applies rotary embeddings inside its
flash-attn mixer (tools/Hetu-Galvatron/galvatron/models/llama/
LlamaModel_sequential.py:14 imports rotary_pos_embedding) and its
Baichuan-13B family uses ALiBi biases (models/baichuan/).  Here RoPE is a
pure pre-transform on q/k and flash attention runs unchanged on the rotated
tensors.

What runs where.  ``_rotary`` is the ``jax.numpy`` form: tables built from
static shapes at the call, the rotation in f32 on a view by heads.  XLA does
NOT fuse it into the projections around it: on the projection's ``[B, S, H,
d]`` view (``seq_axis=1``) the Ouro cell's traced step (ledger, PR 47) spent
about 230 ms of 1,421 on it, 96 times a step: the f32 reshapes between ``[..,
H d]`` and ``[.., H, d]`` are passes over HBM, the two halves of a 128-lane
head are sliced, negated, concatenated and padded, and the tables are
broadcast at every call.  So a multi-head attention layer on the flat path
(``layers/attention.py MultiHeadAttention.layout``) hands q and k together to
``rotary_pair_op``, which on a TPU runs them through the kernel pair
``hetu_rope_fwd`` / ``hetu_rope_bwd`` (``ops/pallas/rotary.py``: on the flat
``[B, S, H d]`` and ``[B, S, KV d]``, one read and one write a tensor) where a
head is whole lane tiles, q and k are both bf16 or both f32 and the sequence
is a multiple of 16, and reads the tables from ONE node a model, sequence
length, head size, base, scaling and count of lanes that turn
(``RopeTables``).  q and k have each their own width (grouped queries), and a
partial rotation (``rotary_dim`` < head size) keeps the kernels: its tables are
``[3, S, d]`` (``_pair_tables``).  Each call counts its choice in
``hetu_kernel_choice_total{kernel="rotary", impl, reason}``: ``pallas``, or
``jnp`` with ``head_dim_not_128_aligned``, ``dtype:<name>``, ``dtype:mixed`` or
``seq_not_16_aligned``; what a mesh and a platform without Mosaic mean is
``dispatch.take``'s rule, and ``_rotary(seq_axis=1)`` then runs on each
tensor's view; under a mesh whose only axis larger than 1 is a ``dp`` that
divides the batch the pair runs per shard under ``shard_map``, each device on
its own sequences (the plan flash attention, the window kernels and
softmax-CE have).  A layer that norms each head before it rotates
(``qk_norm="head"``: SDAR, Qwen3) hands q, k and the two norms' scales to
``qk_norm_rotary_pair_op`` instead: ONE pass of a second kernel pair
(``hetu_qk_norm_rope_fwd`` / ``_bwd``, counted under ``qk_norm_rope``) with
``ops/nn.py _rms_norm``'s own roundings, and where that pair refuses
(``zero_centered``, ``partial_rotation``, and the first pair's reasons)
``_rms_norm`` and ``_rotary`` on the same flat operands' view: such a layer
never goes back to ``[B, H, S, D]`` for its norm.  ``rotary_embedding_op`` (the
layers that stay on ``[B, H, S, D]``: the elementwise gate, grouped queries or
a norm a head on heads that are not whole lane tiles; latent attention's ``_rope_last`` wherever its own kernels,
``ops/pallas/mla_pack.py``, do not run) is ``_rotary`` everywhere.

Conventions match huggingface's ``rotate_half`` (non-interleaved halves),
so HF Llama checkpoints import bit-tight (tests/test_torch_parity.py).
"""

from __future__ import annotations

import math

import jax.numpy as jnp

from .base import SimpleOp, simple_op
from .nn import _rms_norm
from .pallas import dispatch, rotary as kernels
from .pallas.dispatch import shard_axes
from ..graph.node import current_stage


def yarn_scaling(factor, original_max_position_embeddings, beta_fast=32.0,
                 beta_slow=1.0, attention_factor=None):
    """The ``scaling`` of the tables for YaRN as ``transformers`` computes it
    (``rope_type`` "yarn"; arXiv:2309.00071): a hashable tuple.  The
    frequencies whose wavelength the original context holds more than
    ``beta_fast`` times stay, those it holds fewer than ``beta_slow`` times
    are divided by ``factor``, a linear ramp blends between; ``cos`` and
    ``sin`` are multiplied by ``attention_factor`` (default ``0.1 ln factor +
    1``) at every length."""
    if attention_factor is None:
        attention_factor = 0.1 * math.log(factor) + 1.0
    return ("yarn", float(factor), int(original_max_position_embeddings),
            float(beta_fast), float(beta_slow), float(attention_factor))


def _inverse_frequencies(dim, theta, scaling=None):
    """``(inv [dim / 2], what cos and sin are multiplied by)``."""
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    if scaling is None:
        return inv, None
    kind, factor, original, beta_fast, beta_slow, attention_factor = scaling
    assert kind == "yarn", scaling

    def correction(turns):    # the dimension that turns `turns` times
        return (dim * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(correction(beta_fast)), 0)
    high = min(math.ceil(correction(beta_slow)), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    return (1.0 - ramp) * inv + ramp * inv / factor, attention_factor


def _rope_tables(seq_len, dim, theta, pos_offset=0, scaling=None, copies=1):
    # always f32 tables: bf16 positions past ~256 lose the low rotation
    # frequencies entirely.  ``copies``: the sequence is that many copies of
    # ``seq_len / copies`` tokens one behind the other (block diffusion's
    # clean and noised copy), and token i of each turns at position i
    assert seq_len % copies == 0, (seq_len, copies)
    pos = jnp.arange(pos_offset, pos_offset + seq_len // copies,
                     dtype=jnp.float32)
    if copies > 1:
        pos = jnp.tile(pos, copies)
    inv, factor = _inverse_frequencies(dim, theta, scaling)
    freqs = jnp.outer(pos, inv)                       # [S, D/2]
    emb = jnp.concatenate([freqs, freqs], axis=-1)    # [S, D]
    if factor is None:
        return jnp.cos(emb), jnp.sin(emb)
    return jnp.cos(emb) * factor, jnp.sin(emb) * factor


def _rotary(x, *, theta=10000.0, pos_offset=0, seq_axis=-2,
            rotary_dim=None, scaling=None, copies=1):
    """Apply RoPE to [B, H, S, D] (HF rotate_half convention), or, with
    ``seq_axis=1``, to the [B, S, H, D] view of a projection's output.
    ``rotary_dim`` rotates the first ``rotary_dim`` of the ``D`` dimensions
    (frequencies ``theta^(-2i / rotary_dim)``) and passes the rest through
    (partial rotary: GPT-NeoX, Qwen3-Next).  ``scaling``: ``yarn_scaling``'s
    tuple, over the dimensions that turn.  ``copies``: ``_rope_tables``'s."""
    if rotary_dim is not None and rotary_dim != x.shape[-1]:
        assert 0 < rotary_dim < x.shape[-1] and rotary_dim % 2 == 0
        more = {} if scaling is None else {"scaling": scaling}
        if copies != 1:
            more["copies"] = copies
        turned = _rotary(x[..., :rotary_dim], theta=theta,
                         pos_offset=pos_offset, seq_axis=seq_axis, **more)
        return jnp.concatenate([turned, x[..., rotary_dim:]], axis=-1)
    d, s = x.shape[-1], x.shape[seq_axis]
    along = [1] * x.ndim
    along[seq_axis], along[-1] = s, d
    cos, sin = (t.reshape(along)
                for t in _rope_tables(s, d, theta, pos_offset, scaling,
                                      copies))
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., : d // 2], xf[..., d // 2:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return (xf * cos + rotated * sin).astype(x.dtype)


rotary_embedding_op = simple_op(_rotary, "rotary_embedding")


def _pair_tables(*, seq_len, dim, theta, scaling=None, rotary_dim=None,
                 copies=1):
    """``[2, S, D]`` f32: ``cos`` and ``sin±``, the sine with
    ``rotate_half``'s sign on it (``-sin`` on the first ``D / 2`` lanes), so
    that ``rotate_half(x) sin = roll(x, D / 2) sin±``.  Where only the first
    ``rotary_dim = r < D`` lanes turn (frequencies over ``r``), ``[3, S, D]``:
    ``cos`` (1 from ``r`` on), ``sA`` (``+sin`` on ``[r / 2, r)``) and ``sB``
    (``-sin`` on ``[0, r / 2)``), zero elsewhere: ``roll(x, r / 2) sA +
    roll(x, D - r / 2) sB`` (``ops/pallas/rotary.py``)."""
    r = rotary_dim or dim
    cos, sin = _rope_tables(seq_len, r, theta, scaling=scaling,
                            copies=copies)
    if r == dim:
        return jnp.stack(
            [cos, jnp.where(jnp.arange(dim) < dim // 2, -sin, sin)])
    assert 0 < r < dim and r % 2 == 0, (r, dim)
    rest = ((0, 0), (0, dim - r))
    lane = jnp.arange(r)
    return jnp.stack(
        [jnp.pad(cos, rest, constant_values=1.0),
         jnp.pad(jnp.where(lane >= r // 2, sin, 0.0), rest),
         jnp.pad(jnp.where(lane < r // 2, -sin, 0.0), rest)])


_pair_tables_op = simple_op(_pair_tables, "rope_tables")


class RopeTables:
    """The ``_pair_tables`` nodes of one model: ONE a sequence length, head
    size, base, scaling (``yarn_scaling``; None: plain), count of lanes that
    turn (``rotary_dim``; None: all), count of copies of the tokens the
    sequence holds (``copies``; 1: positions ``0 .. S - 1``) and pipeline stage,
    made for the layer that asks first and read by every layer (and every application of a layer) after it, outside any
    ``ht.remat()`` group, which then reads it as an input.  An attention layer
    has its own unless its model hands all its layers one
    (``models/llama.py``)."""

    def __init__(self):
        self.nodes = {}

    def __call__(self, seq_len, dim, theta, scaling=None, rotary_dim=None,
                 copies=1):
        # what a plain, whole rotation does not have is not among its
        # node's attributes
        more = {name: value for name, value in (
            ("scaling", scaling),
            ("rotary_dim", None if rotary_dim == dim else rotary_dim),
            ("copies", None if copies == 1 else copies))
            if value is not None}
        key = (seq_len, dim, float(theta), current_stage()) + tuple(
            more.items())
        if key not in self.nodes:
            node = self.nodes[key] = _pair_tables_op(
                seq_len=seq_len, dim=dim, theta=key[2], **more)
            node.remat_scope = None
        return self.nodes[key]


class RotaryPairOp(SimpleOp):
    """``(q, k, tables) -> (q', k')`` on ``[B, S, H d]`` (or a caller's
    ``[B S, H d]``): the kernel pair where ``dispatch.take`` says so, else
    ``impl`` (``_rotary``) on each tensor's view by heads."""

    def _compute(self, input_vals, ctx):
        q, k, tables = input_vals
        seq_len, d = tables.shape[1:]
        q, k = (x.reshape(-1, seq_len, x.shape[-1]) for x in (q, k))
        turned = self.attrs.get("rotary_dim")
        # under a mesh whose one axis splits the batch the kernels run on
        # each device's own sequences and see no mesh; any other mesh is
        # ``take``'s to refuse
        over = _batch_axes(ctx.mesh, q.shape[0])
        if dispatch.take("rotary", None if over else ctx.mesh,
                         kernels.unsupported(q, k, head_dim=d)):
            if not over:
                return kernels.rope(q, k, tables, turned)
            from jax import shard_map
            from jax.sharding import PartitionSpec as P
            # pallas out_shapes carry no varying-axes annotations
            return shard_map(
                lambda q, k, t: kernels.rope(q, k, t, turned), mesh=ctx.mesh,
                in_specs=(P(over), P(over), P()), out_specs=(P(over),) * 2,
                check_vma=False)(q, k, tables)
        return tuple(
            self.impl(x.reshape(*x.shape[:2], -1, d), seq_axis=1,
                      **self.attrs).reshape(x.shape) for x in (q, k))


def _batch_axes(mesh, batch):
    """The mesh axes a per-shard rotation splits its batch over
    (``dispatch.shard_axes``: ``dp`` where it divides the batch and no other
    axis is larger than 1), ``()`` where there is none."""
    if mesh is None:
        return ()
    why, axes = shard_axes(mesh, {"dp": batch})
    return () if why is not None else axes["dp"]


_rotary_pair_op = simple_op(_rotary, "rotary_pair", node_cls=RotaryPairOp)
pair_item_op = simple_op(lambda pair, *, index: pair[index], "pair_item")


def _turn_attrs(tables):
    """What a pair node's ``jax.numpy`` form needs of its tables' node."""
    return {key: tables.attrs[key]
            for key in ("theta", "scaling", "rotary_dim", "copies")
            if key in tables.attrs}


def rotary_pair_op(q, k, tables):
    """The nodes of q ``[B, S, H d]`` and k ``[B, S, KV d]`` rotated, both
    from one node; ``tables``: a ``RopeTables`` node of their sequence length,
    head size, base, scaling and lanes that turn."""
    pair = _rotary_pair_op(q, k, tables, **_turn_attrs(tables))
    return pair_item_op(pair, index=0), pair_item_op(pair, index=1)


class NormRotaryPairOp(SimpleOp):
    """``(q, k, wq, wk, tables) -> (q', k')`` on ``[B, S, H d]``: a norm a
    head (``ops/nn.py _rms_norm`` under ``wq``, ``wk [d]``) and then the
    rotation, ONE pass of the kernel pair ``hetu_qk_norm_rope_fwd`` / ``_bwd``
    where ``dispatch.take`` says so (label ``qk_norm_rope``), else ``impl``
    (``_rms_norm``, then ``_rotary``) on each tensor's view by heads: what the
    kernels refuse stays on the flat path too."""

    def _compute(self, input_vals, ctx):
        q, k, wq, wk, tables = input_vals
        seq_len, d = tables.shape[1:]
        q, k = (x.reshape(-1, seq_len, x.shape[-1]) for x in (q, k))
        attrs = self.attrs
        if dispatch.take("qk_norm_rope", ctx.mesh, kernels.norm_unsupported(
                q, k, wq, wk, head_dim=d, rotary_dim=attrs.get("rotary_dim"),
                zero_centered=attrs["zero_centered"])):
            return kernels.norm_rope(q, k, wq, wk, tables, attrs["eps"])
        return tuple(
            self.impl(x.reshape(*x.shape[:2], -1, d), w, **attrs
                      ).reshape(x.shape) for x, w in ((q, wq), (k, wk)))


def _norm_rotary(view, scale, *, eps, zero_centered, **turn):
    """A norm a head and the rotation on ``[B, S, H, d]``, ``jax.numpy``."""
    return _rotary(_rms_norm(view, scale, eps, zero_centered), seq_axis=1,
                   **turn)


_norm_rotary_pair_op = simple_op(_norm_rotary, "qk_norm_rotary_pair",
                                 node_cls=NormRotaryPairOp)


def qk_norm_rotary_pair_op(q, k, q_scale, k_scale, tables, *, eps,
                           zero_centered=False):
    """``rotary_pair_op`` behind a norm a head: the nodes of q ``[B, S, H
    d]`` and k ``[B, S, KV d]``, each head normed under ``q_scale`` /
    ``k_scale [d]`` (``_rms_norm(eps, zero_centered)``) and rotated, both from
    one node."""
    pair = _norm_rotary_pair_op(q, k, q_scale, k_scale, tables, eps=eps,
                                zero_centered=zero_centered,
                                **_turn_attrs(tables))
    return pair_item_op(pair, index=0), pair_item_op(pair, index=1)


def _repeat_kv(x, *, n_rep):
    """[B, KV, S, D] -> [B, KV*n_rep, S, D] for grouped-query attention.

    Broadcast + reshape (not jnp.repeat): XLA lowers it to a view-like
    broadcast that fuses into the attention einsum instead of
    materializing the repeated K/V in HBM.
    """
    if n_rep == 1:
        return x
    b, kv, s, d = x.shape
    x = jnp.broadcast_to(x[:, :, None, :, :], (b, kv, n_rep, s, d))
    return x.reshape(b, kv * n_rep, s, d)


repeat_kv_op = simple_op(_repeat_kv, "repeat_kv")


def alibi_slopes(num_heads):
    """Per-head ALiBi slopes (Press et al., the published closed form)."""
    import math

    def pow2_slopes(n):
        start = 2.0 ** (-(2.0 ** -(math.log2(n) - 3)))
        return [start * (start ** i) for i in range(n)]

    if math.log2(num_heads).is_integer():
        return pow2_slopes(num_heads)
    closest = 2 ** math.floor(math.log2(num_heads))
    extra = pow2_slopes(2 * closest)[0::2][: num_heads - closest]
    return pow2_slopes(closest) + extra


def _alibi_bias(q, *, num_heads):
    """Additive [1, H, S, S] ALiBi bias from a [B, H, S, D] query.

    Only the linear -slope*(i-j) term; the causal cut is the attention
    op's ``causal`` flag (reference Baichuan builds both into one mask).
    """
    s = q.shape[-2]
    slopes = jnp.asarray(alibi_slopes(num_heads), dtype=jnp.float32)
    rel = jnp.arange(s, dtype=jnp.float32)[None, :] \
        - jnp.arange(s, dtype=jnp.float32)[:, None]   # j - i  (<= 0 past)
    bias = slopes[:, None, None] * rel[None, :, :]    # [H, S, S]
    return bias[None].astype(q.dtype)


alibi_bias_op = simple_op(_alibi_bias, "alibi_bias")
