"""Pallas TPU dropout keep mask, drawn from the chip's own generator.

``jax.random.bernoulli`` lowers, under ``rng_impl="rbg"``, to a stand-alone
``rng-bit-generator`` that writes one u32 to HBM for every element of the
activation, and a compare fusion that reads the words back.  Under a mesh
the generator does not partition: every device draws the words of the
*global* batch and uses its own share of them.

  ``hetu_dropout_mask``: grid over row blocks of the ``[rows, lanes]`` view
  of an activation.  A block reseeds the per-core PRNG with (seed, block
  index), draws its words in VMEM, compares them with the u32 threshold
  flash attention's in-kernel dropout uses (``tile_keep``) and stores the
  keep mask as int8.  The words never reach HBM.

The kernel needs a shape, not the activation: the caller applies the mask
with ``jnp.where``, so XLA fuses the select into whatever produced the
activation and autodiff saves the narrow mask.  Under a mesh
(``sharded_dropout_mask``) each device draws the mask of its own rows.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dispatch import interpret
from .flash_attention import tile_keep, shard_seed

_LANES = 128
_SUBLANES = 32            # int8 tiles are (32, 128)
_BLOCK_ELEMS = 512 * 768  # words a block draws: 1.5 MiB of VMEM


def unsupported(shape):
    """Why a keep mask of this (per-shard) shape is not drawn by the
    kernel, or None: its ``[rows, lanes]`` view must be whole int8 tiles
    and reshape back for free."""
    if len(shape) < 2 or shape[-1] % _LANES:
        return "last_dim_not_128_aligned"
    if shape[-2] % _SUBLANES or not all(shape):
        return "rows_not_32_aligned"
    return None


def _mask_kernel(seed_ref, out_ref, *, keep_prob):
    keep = tile_keep(out_ref.shape, seed_ref, pl.program_id(0), keep_prob)
    out_ref[...] = keep.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _mask(seed, shape, keep_prob):
    rows, lanes = math.prod(shape[:-1]), shape[-1]
    block = min(rows, max(_SUBLANES,
                          _BLOCK_ELEMS // lanes // _SUBLANES * _SUBLANES))
    mask = pl.pallas_call(
        functools.partial(_mask_kernel, keep_prob=keep_prob),
        name="hetu_dropout_mask",
        interpret=interpret(),
        grid=(pl.cdiv(rows, block),),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((block, lanes), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, lanes), jnp.int8),
    )(seed)
    return mask.reshape(shape)


def dropout_mask(seed, shape, keep_prob):
    """int8 ``shape``: 1 where an element is kept (probability
    ``floor(keep_prob * 2**32) / 2**32``), else 0.  ``seed`` is int32[1];
    the same seed gives the same mask."""
    why = unsupported(shape)
    if why is not None:
        raise ValueError(f"no hetu_dropout_mask of {tuple(shape)}: {why}")
    return _mask(seed, tuple(shape), float(keep_prob))


def sharded_dropout_mask(mesh, seed, shape, keep_prob, *, batch_axes):
    """:func:`dropout_mask` of the global ``shape`` inside a GSPMD mesh
    program: ``pallas_call`` does not partition, so each device draws the
    mask of its own rows under ``shard_map`` (dim 0 split over
    ``batch_axes``, which must divide it), the seed offset by the shard's
    index so that shards do not repeat one mask."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    batch_axes = tuple(batch_axes)
    shards = math.prod(mesh.shape[a] for a in batch_axes)
    local_shape = (shape[0] // shards,) + tuple(shape[1:])

    def local(sd):
        return dropout_mask(shard_seed(sd, batch_axes), local_shape,
                            keep_prob)

    # pallas out_shapes carry no varying-axes annotations
    return shard_map(local, mesh=mesh, in_specs=(P(),),
                     out_specs=P(batch_axes, *[None] * (len(shape) - 1)),
                     check_vma=False)(seed)
