"""The selective state-space recurrence of Mamba-1 (Gu and Dao 2023,
arXiv:2312.00752; the mixers of the SambaY decoders, arXiv:2507.06607): a
state ``h [C, N]`` a sequence (``C`` channels, ``N`` states a channel) that
starts at zero, and for each position ``t``::

    h = exp(delta_t[:, None] A) * h + (delta_t * u_t)[:, None] B_t[None, :]
    y_t = h C_t                    (A [C, N] < 0, delta_t [C] > 0; B_t, C_t [N])

Every channel AND state has a decay of its own, so the chunked product form
of ``ops/ssd.py`` (one scalar decay a head) does not compute it.  The skip
``D u_t``, the softplus in front of ``delta`` and the gate behind ``y`` are
the layer's (``layers/mamba1.py``).

``recurrent_selective_scan`` is that loop, one position at a time: what the
tests hold the others to (``state_dtype`` is its negative control's).

``selective_scan_jnp`` is the same loop in chunks of ``chunk`` positions
under ``jax.checkpoint``: the backward pass keeps the state at chunk edges
only (``S / chunk`` states of ``[B, C, N]`` f32; the loop kept whole would
hold ``S`` of them, 5.4 GB a 16,384-token sequence at 5,120 x 16) and runs a
chunk's positions again.

Precision: ``delta``, ``A``, every decay, the state and ``y`` are f32
whatever the compute type of ``u``; ``B`` and ``C`` are taken to f32.

Shapes: ``u [b, S, C]``, ``delta [b, S, C]`` (after its softplus), ``A [C,
N]``, ``B, C [b, S, N]``; returns ``y [b, S, C]`` f32.

What runs where.  ``selective_scan`` is what the layer's ``hetu_ssm_scan``
node calls.  On a TPU it runs as two Pallas kernels, ``hetu_s6_fwd`` and
``hetu_s6_bwd`` (``ops/pallas/selective_scan.py``, a ``jax.custom_vjp``),
where it can read that they apply (``unsupported``: channels in whole lane
tiles, states a multiple of 8 up to 128, ``u`` bf16 or f32).  Each call counts
its choice at trace time in ``hetu_kernel_choice_total{kernel=
"selective_scan", impl, reason}`` and its entry in
``hetu_s6_entry_total{path}`` (``pallas`` / ``xla``); what a mesh (which the
scan node sees, ``ops/base.py KernelOp``) and a platform without Mosaic mean
is ``dispatch.take``'s rule.  The kernels themselves run anywhere when called
directly (interpret mode on the CPU): ``tests/test_selective_scan.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import telemetry

#: positions a chunk: what the backward pass runs again from a kept state
CHUNK = 128

_F32 = jnp.float32


def _step(A):
    def step(h, t):
        u_t, d_t, b_t, c_t = t                     # [b, C], [b, C], [b, N] x 2
        h = jnp.exp(d_t[..., None] * A) * h.astype(_F32) + (
            (d_t * u_t)[..., None] * b_t[:, None, :])
        return h, jnp.sum(h * c_t[:, None, :], axis=-1)
    return step


def _time_major(u, delta, B, C):
    return tuple(jnp.moveaxis(t.astype(_F32), 1, 0) for t in (u, delta, B, C))


def recurrent_selective_scan(u, delta, A, B, C, state_dtype=_F32):
    """The recurrence, one position at a time; returns ``(y, last state)``.
    ``state_dtype`` is the type the state is carried in between positions."""
    b, _, ch = u.shape
    step = _step(A.astype(_F32))

    def carried(h, t):
        h, y = step(h, t)
        return h.astype(state_dtype), y
    h, y = jax.lax.scan(carried, jnp.zeros((b, ch, A.shape[1]), state_dtype),
                        _time_major(u, delta, B, C))
    return jnp.moveaxis(y, 0, 1), h.astype(_F32)


def selective_scan_jnp(u, delta, A, B, C, chunk=CHUNK):
    """The recurrence in chunks whose inside the backward pass runs again:
    what the kernels are held to, and what runs wherever they do not."""
    b, S, ch = u.shape
    pad = -S % chunk
    if pad:
        # a position of padding decays nothing and writes nothing (delta 0)
        u, delta, B, C = (jnp.pad(t, ((0, 0), (0, pad), (0, 0)))
                          for t in (u, delta, B, C))
    n = (S + pad) // chunk
    step = _step(A.astype(_F32))

    @jax.checkpoint
    def one(h, xs):
        return jax.lax.scan(step, h, xs)
    xs = tuple(t.reshape((n, chunk) + t.shape[1:])
               for t in _time_major(u, delta, B, C))
    _, y = jax.lax.scan(one, jnp.zeros((b, ch, A.shape[1]), _F32), xs)
    return jnp.moveaxis(y.reshape((n * chunk, b, ch)), 0, 1)[:, :S]


def count_entry(path):
    """One more scan traced, in ``hetu_s6_entry_total{path}``."""
    telemetry.get_registry().counter(
        "hetu_s6_entry_total",
        "Mamba-1 selective scans traced, by the form that ran (pallas: the "
        "kernel pair hetu_s6_fwd / hetu_s6_bwd; xla: the chunked jax.numpy "
        "form)", labels=("path",)).labels(path=path).inc()


def entries():
    """``{path: count}`` of ``hetu_s6_entry_total``."""
    from .pallas import dispatch
    return {lab["path"]: n for lab, n in dispatch.counted(
        "hetu_s6_entry_total")}


def selective_scan(u, delta, A, B, C, chunk=CHUNK):
    """The Pallas kernel pair where ``dispatch.take`` and its rule allow,
    else the ``jax.numpy`` form."""
    from .pallas import dispatch, selective_scan as kernels
    if dispatch.take("selective_scan", None, kernels.unsupported(u, A)):
        count_entry("pallas")
        return kernels.s6(u, delta, A, B, C)
    return selective_scan_xla(u, delta, A, B, C, chunk)


def selective_scan_xla(u, delta, A, B, C, chunk=CHUNK):
    """``selective_scan_jnp``, counted as the ``xla`` entry: what a scan node
    under a mesh calls itself."""
    count_entry("xla")
    return selective_scan_jnp(u, delta, A, B, C, chunk)
