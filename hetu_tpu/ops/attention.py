"""Attention ops.

The reference composes attention from batched matmuls + softmax graph nodes
(layers/attention.py); there is no fused kernel.  Here scaled-dot-product
attention is ONE graph op so the executor can lower it to the Pallas flash
attention kernel on TPU (ops/pallas/flash_attention.py) and fall back to a
fusable jnp composition elsewhere — the TPU answer to cudnn-style fused MHA
and the building block the reference lacks for long-context (ring/blockwise)
variants.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import telemetry
from ..graph.node import Op

_FLASH_MIN_SEQ = 256  # below this the jnp path is faster (kernel overheads)


def _head_view(x, num_heads, rep=1):
    """[B, S, H/rep*D] -> [B, S, H, D]: a free view; with ``rep`` > 1 (keys
    and values of grouped queries) a key head under each of its ``rep`` query
    heads, as ``ops/rotary.py _repeat_kv`` on the other axis."""
    b, s, width = x.shape
    kv = num_heads // rep
    x = x.reshape(b, s, kv, width // kv)
    if rep == 1:
        return x
    return jnp.broadcast_to(x[:, :, :, None, :], (b, s, kv, rep, x.shape[-1])
                            ).reshape(b, s, num_heads, x.shape[-1])


def _split_heads(x, num_heads, rep=1):
    """[B, S, H/rep*D] -> [B, H, S, D]: a transpose, not a view."""
    return _head_view(x, num_heads, rep).transpose(0, 2, 1, 3)


def block_diffusion_mask(positions, block):
    """``[2L, 2L]`` bool, query on key, over a clean copy of ``L = positions
    / 2`` tokens and then their noised copy, blocks of ``block`` tokens (``b(i)
    = i // block`` on a token's index in its half): clean on clean ``b(j) <=
    b(i)``, noised on clean ``b(j) < b(i)``, noised on noised ``b(j) ==
    b(i)``, clean on noised never.  The noised block ``b`` sees the clean
    blocks before it and itself, which is what generation sees when it
    denoises block ``b``."""
    half = positions // 2
    at = jnp.arange(positions)
    noised, b = at >= half, (at % half) // block
    qn, kn, qb, kb = noised[:, None], noised[None, :], b[:, None], b[None, :]
    return jnp.where(kn, qn & (kb == qb),
                     jnp.where(qn, kb < qb, kb <= qb))


def _flash_plan(q, k, v, mask, keep, mesh, num_heads=None, window=None,
                block_diffusion=None):
    """Decide how attention lowers for these operands: ``[B, H, S, D]``,
    or ``[B, S, H*D]`` with ``num_heads``, which is planned as the 4-D
    array it is a view of; k and v may then be ``[B, S, KV*D]`` of fewer
    heads (grouped queries), where a head is whole lane tiles.

    Returns ``(reason, batch_axes, head_axes)``: ``reason`` is None when
    the Pallas flash kernel runs (under ``shard_map`` over the named mesh
    axes when either tuple is non-empty), else why the jnp composition
    runs.  The kernel is taken on ``tpu`` only — interpret mode would
    make every CPU attention test an emulated kernel run — for sequences
    of at least ``_FLASH_MIN_SEQ`` and 8-aligned head sizes in
    [32, 512]."""
    from .pallas import dispatch
    from .pallas.flash_attention import heads_views, unsupported
    if not dispatch.mosaic():
        return f"platform:{dispatch.platform()}", (), ()
    if num_heads is not None:
        q, k, v = heads_views(q, k, v, num_heads)
    elif k.ndim == 4 and k.shape[1] != q.shape[1]:
        return "kv_heads_differ_4d", (), ()
    why = unsupported(q, k, v, mask, keep, window, block_diffusion)
    if why is not None:
        return why, (), ()
    if (num_heads is not None and v.shape[-1] != q.shape[-1]
            and (q.shape[-1] % 128 or v.shape[-1] % 128)):
        return "two_head_sizes_not_128_aligned", (), ()
    if q.shape[-2] < _FLASH_MIN_SEQ:
        return f"seq<{_FLASH_MIN_SEQ}", (), ()
    if not all(32 <= d <= 512 and d % 8 == 0
               for d in (q.shape[-1], v.shape[-1])):
        return "head_dim_not_8_aligned_in_32_512", (), ()
    # under a mesh: per shard, batch over 'dp' and heads over 'tp' (the key
    # heads, which divide the query heads)
    why, axes = dispatch.shard_axes(mesh, {"dp": q.shape[0],
                                           "tp": k.shape[1]})
    return why, axes["dp"], axes["tp"]


class ScaledDotProductAttentionOp(Op):
    """q, k, v ``[B, H, S, D]`` -> ``[B, H, S, D]``; with ``num_heads``,
    the projections' ``[B, S, H*D]`` -> ``[B, S, H*D]``: the flash kernel
    then reads and writes the heads in place, and the jnp composition goes
    through the free ``[B, S, H, D]`` view.  There k and v may be ``[B, S,
    KV*D]`` of fewer heads (grouped queries; ``rep = H / KV`` is read from
    the widths): the kernel reads a query head's key head where it lies, the
    jnp composition repeats the key heads on the view.  V's heads may be of
    another size than q's and k's (latent attention: 256 / 128), whole lane
    tiles each.  A node built with ``num_heads`` whose operands come ``[B, H,
    S, D]`` all the same (a layer that chooses its layout when it is traced)
    attends them as that.

    ``window`` (``WindowAttentionOp``, causal): position ``i`` sees the keys
    ``j`` with ``0 <= i - j < window``, its own among them (512 keys at 512,
    not 513); the kernels then go by ``hetu_swa_fwd`` / ``hetu_swa_bwd`` and
    skip the blocks outside the band.  With a window there is no dropout and
    no ring: refused where the node is built or evaluated.

    ``block_diffusion=K`` (not causal, no mask, dropout or ring): the sequence
    is a clean copy of ``L`` tokens and then their noised copy, and a position
    sees what ``block_diffusion_mask`` shows; the kernels then go by
    ``hetu_flash_fwd_bd`` / ``hetu_flash_bwd_bd`` and walk the tiles that hold
    a visible pair.  The node's type is this one's (the flash passes' events
    are counted on the nodes of this type: ``chipbench/tests/
    test_attention_yardstick.py``); its ``kind`` says which mask it has.

    ``eva=(window, chunk)`` with ``summaries=(ks, vs)`` (causal, ``[B, S,
    H*D]`` with ``num_heads``, no mask or dropout): EVA attention
    (``ops/eva.py``): the keys of a position's own aligned window exactly and
    one summary a chunk for the windows before it under one softmax; the
    kernels then go by ``hetu_eva_fwd`` / ``hetu_eva_bwd``.  The node's type is
    this one's too (a model whose every layer is of this kind has these for
    its attention passes); its ``kind`` is ``eva``."""

    #: the keys a position sees; None: all (that ``causal`` leaves)
    window = None
    #: the block length of the block-diffusion mask; None: no such mask
    block_diffusion = None
    #: ``differential``: the heads are the two halves of query pairs on one
    #: value (``layers/attention.py DifferentialAttention``); ``cross`` with
    #: it: the keys and values are another layer's nodes
    form = None
    #: ``(window, chunk)`` of EVA attention; the summaries are then the node's
    #: last two inputs
    eva = None

    @property
    def kind(self):
        """``full``, ``window`` or ``block_diffusion``, behind
        ``differential_`` where the node is a differential layer's
        (``differential_cross`` where its keys and values are another
        layer's): the label of ``hetu_attn_layers_total``."""
        if self.block_diffusion is not None:
            return "block_diffusion"
        if self.eva is not None:
            return "eva"
        kind = "full" if self.window is None else "window"
        if self.form is None:
            return kind
        return "differential_" + ("cross" if self.form == "cross" else kind)

    def __init__(self, q, k, v, mask=None, causal=False, scale=None,
                 dropout_keep=1.0, num_heads=None, block_diffusion=None,
                 form=None, eva=None, summaries=(), name=None):
        inputs = [q, k, v] + ([mask] if mask is not None else [])
        super().__init__(*inputs, *summaries, name=name)
        if eva is not None:
            assert (causal and mask is None and dropout_keep >= 1.0
                    and num_heads and self.window is None
                    and block_diffusion is None and len(summaries) == 2), (
                "EVA attention is causal on [B, S, H*D], with its two "
                "summaries and no key mask, dropout or window beside it")
            self.eva = tuple(map(int, eva))
        if form is not None:
            assert form in ("differential", "cross"), form
            self.form = form
        if block_diffusion is not None:
            assert (not causal and mask is None and dropout_keep >= 1.0
                    and self.window is None and block_diffusion >= 1), (
                "the block-diffusion mask stands alone: no causal flag, key "
                "mask, dropout on the probabilities or window beside it")
            self.block_diffusion = int(block_diffusion)
        telemetry.get_registry().counter(
            "hetu_attn_layers_total",
            "Attention nodes built, by kind (full: every key the mask "
            "leaves; window: the last `window` keys; block_diffusion: a clean "
            "and a noised copy under the block-diffusion mask; differential_"
            "full / _window / _cross: the halves of query pairs as heads on "
            "one value, on the layer's own keys or on another layer's; eva: "
            "exact keys inside an aligned window and one summary a chunk "
            "before it)",
            labels=("kind",),
        ).labels(kind=self.kind).inc()
        self.has_mask = mask is not None
        self.num_heads = num_heads
        self.causal = causal
        self.scale = scale
        self.dropout_keep = dropout_keep

    @property
    def needs_rng(self):
        return self.dropout_keep < 1.0

    def _compute(self, input_vals, ctx):
        q, k, v = input_vals[:3]
        mask = input_vals[3] if self.has_mask else None
        heads = self.num_heads
        if self.eva is not None:
            from .eva import eva_attention
            return eva_attention(q, k, v, *input_vals[-2:],
                                 window=self.eva[0], chunk=self.eva[1],
                                 num_heads=heads, scale=self.scale,
                                 mesh=ctx.mesh)
        if heads is None or q.ndim == 4:
            return self._attend(q, k, v, mask, ctx, None)
        if self._stays_in_place(q, k, v, mask, ctx):
            return self._attend(q, k, v, mask, ctx, heads)
        # the ring walks [B, H, S, D], and so does the kernel where a
        # shard's heads do not come in lane-aligned groups
        rep = q.shape[-1] // k.shape[-1]
        out = self._attend(*(_split_heads(x, heads, n) for x, n in (
            (q, 1), (k, rep), (v, rep))), mask, ctx, None)
        return out.transpose(0, 2, 1, 3).reshape(q.shape[:2] + (-1,))

    def _keep(self, ctx):
        return self.dropout_keep if ctx.training else 1.0

    def _stays_in_place(self, q, k, v, mask, ctx):
        """Whether [B, S, H*D] operands are attended as they lie: the jnp
        composition reads them through a free view, the kernel needs each
        shard's heads in groups of 128 lanes, the ring takes neither."""
        from .pallas.flash_attention import heads_per_program
        if ctx.mesh is not None and ctx.mesh.shape.get("cp", 1) > 1:
            return False
        why, _, head_axes = _flash_plan(q, k, v, mask, self._keep(ctx),
                                        ctx.mesh, self.num_heads, self.window,
                                        self.block_diffusion)
        if why is not None:
            return True
        shards = 1
        for axis in head_axes:
            shards *= ctx.mesh.shape[axis]
        return heads_per_program(self.num_heads // shards,
                                 q.shape[-1] // self.num_heads) > 0

    def _attend(self, q, k, v, mask, ctx, heads):
        """``heads`` is None for [B, H, S, D] operands, the head count for
        [B, S, H*D]."""
        d = q.shape[-1] // (heads or 1)
        scale = self.scale if self.scale is not None else 1.0 / (d ** 0.5)
        # long-context: when the executor's mesh has a 'cp' axis, the
        # sequence dim is context-sharded — lower to flash ring attention
        # (K/V blocks rotate the ICI ring; parallel/context_parallel.py).
        # Dropout/masks stay on the single-device paths.
        if ((self.window is not None or self.block_diffusion is not None)
                and ctx.mesh is not None
                and ctx.mesh.shape.get("cp", 1) > 1):
            raise NotImplementedError(
                "attention with a window or the block-diffusion mask over a "
                "context-parallel mesh: the ring's offsets are not built for "
                "it")
        if (ctx.mesh is not None and "cp" in ctx.mesh.shape
                and ctx.mesh.shape["cp"] > 1 and mask is None
                and self.dropout_keep >= 1.0 and q.ndim == 4
                and q.shape == k.shape == v.shape
                # shard_map dies opaquely on indivisible shapes — route
                # those to the flash/jnp paths below instead
                and q.shape[2] % ctx.mesh.shape["cp"] == 0
                and ("dp" not in ctx.mesh.shape
                     or q.shape[0] % ctx.mesh.shape["dp"] == 0)):
            impl = getattr(ctx, "cp_impl", "ring")
            if (impl == "ulysses"
                    and q.shape[1] % ctx.mesh.shape["cp"] == 0):
                from ..parallel.context_parallel import ulysses_attention
                return ulysses_attention(ctx.mesh, q, k, v,
                                         causal=self.causal, scale=scale)
            from ..parallel.context_parallel import ring_attention
            return ring_attention(ctx.mesh, q, k, v, causal=self.causal,
                                  scale=scale)
        keep = self._keep(ctx)
        why, batch_axes, head_axes = _flash_plan(
            q, k, v, mask, keep, ctx.mesh, heads, self.window,
            self.block_diffusion)
        from .pallas import dispatch
        if dispatch.record("flash_attention", why):
            from .pallas.flash_attention import (flash_attention,
                                                 sharded_flash_attention)
            seed = None
            if keep < 1.0:
                seed = jax.random.bits(ctx.rng_for(self), (1,),
                                       "uint32").astype(jnp.int32)
            kw = dict(mask=mask, causal=self.causal, scale=scale,
                      dropout_keep=keep, seed=seed, num_heads=heads,
                      window=self.window)
            if self.block_diffusion is not None:
                kw["block_diffusion"] = self.block_diffusion
            if batch_axes or head_axes:
                return sharded_flash_attention(
                    ctx.mesh, q, k, v, batch_axes=batch_axes,
                    head_axes=head_axes, **kw)
            return flash_attention(q, k, v, **kw)
        qk, pv = "bhqd,bhkd->bhqk", "bhqk,bhkd->bhqd"
        if heads is not None:
            rep = q.shape[-1] // k.shape[-1]
            q, k, v = (_head_view(x, heads, n) for x, n in (
                (q, 1), (k, rep), (v, rep)))
            qk, pv = "bqhd,bkhd->bhqk", "bhqk,bkhd->bqhd"
        scores = jnp.einsum(qk, q, k,
                            preferred_element_type=jnp.float32) * scale
        if self.causal:
            s_q, s_k = scores.shape[-2], scores.shape[-1]
            iq = jnp.arange(s_q)[:, None]
            ik = jnp.arange(s_k)[None, :]
            seen = iq >= ik - (s_k - s_q)
            if self.window is not None:
                seen = seen & (iq - ik + (s_k - s_q) < self.window)
            scores = jnp.where(seen, scores, -1e9)
        if self.block_diffusion is not None:
            scores = jnp.where(
                block_diffusion_mask(scores.shape[-1], self.block_diffusion),
                scores, -1e9)
        if mask is not None:
            scores = scores + mask
        probs = jax.nn.softmax(scores, axis=-1)
        if self.dropout_keep < 1.0 and ctx.training:
            keep = jax.random.bernoulli(ctx.rng_for(self), self.dropout_keep,
                                        probs.shape)
            probs = jnp.where(keep, probs / self.dropout_keep, 0.0)
        out = jnp.einsum(pv, probs.astype(v.dtype), v,
                         preferred_element_type=jnp.float32).astype(v.dtype)
        return out if heads is None else out.reshape(*out.shape[:2], -1)


class WindowAttentionOp(ScaledDotProductAttentionOp):
    """Causal attention over the last ``window`` keys: a node type of its own,
    so that a reader of the graph tells the two kinds of layer apart as a
    reader of the trace tells their kernels apart by name."""

    def __init__(self, q, k, v, window, **kw):
        assert kw.get("causal") and window >= 1, (
            "a window is over the keys behind a position", window)
        assert kw.get("dropout_keep", 1.0) >= 1.0, (
            "dropout on the probabilities is not built with a window")
        self.window = int(window)
        super().__init__(q, k, v, **kw)


def scaled_dot_product_attention_op(q, k, v, mask=None, causal=False,
                                    scale=None, dropout_keep=1.0,
                                    num_heads=None, window=None,
                                    block_diffusion=None, form=None,
                                    eva=None, summaries=(), name=None):
    kw = dict(mask=mask, causal=causal, scale=scale,
              dropout_keep=dropout_keep, num_heads=num_heads, name=name)
    if form is not None:
        kw["form"] = form
    if eva is not None:
        assert window is None, "a window or summaries behind one, not both"
        kw.update(eva=eva, summaries=summaries)
    if window is not None:
        assert block_diffusion is None, "a window or the block mask, not both"
        return WindowAttentionOp(q, k, v, window, **kw)
    if block_diffusion is not None:
        kw["block_diffusion"] = block_diffusion
    return ScaledDotProductAttentionOp(q, k, v, **kw)
