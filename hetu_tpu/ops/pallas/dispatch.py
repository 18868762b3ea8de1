"""Kernel selection shared by the Pallas ops.

Three decisions live here so they are made once and the same way for
every kernel file: which platform the program is being compiled for,
whether a ``pallas_call`` runs in interpret mode, and each
kernel-versus-``jnp`` choice an op makes while it is traced, with its
record.  A kernel with a per-shard form has a plan of its own that reads
the mesh (``shard_axes``) and hands ``record`` its reason; every other
kernel asks ``take``.

A choice is counted in the telemetry registry
(``hetu_kernel_choice_total{kernel, impl, reason}``) and, when the
``jnp`` form is taken on a TPU, logged as a warning: a step that ran on
the chip without its Mosaic kernels must say so.  Selection happens at
trace time, so the cost is a few calls per compilation and nothing per
step.
"""

from __future__ import annotations

import contextlib
import logging
import threading

import jax
from jax.ad_checkpoint import checkpoint_name

from ... import telemetry

_log = logging.getLogger(__name__)


def platform():
    """The platform jitted programs compile for in this process."""
    return jax.default_backend()


def interpret():
    """Interpret mode for ``pallas_call``: only on ``cpu``, which has no
    Mosaic backend, so that the kernels stay testable there."""
    return platform() == "cpu"


def mosaic():
    """True when ``pallas_call`` lowers to a Mosaic custom call."""
    return platform() == "tpu"


def shard_axes(mesh, dims):
    """Which mesh axes split which operand dim of a per-shard kernel.

    ``pallas_call`` does not partition under GSPMD, so under a mesh a
    kernel runs per shard through ``shard_map``.  ``dims`` maps a mesh
    axis name to the size of the operand dim it may split (``{"dp":
    batch, "tp": heads}``).  Returns ``(reason, axes)``: ``axes[name]`` is
    ``(name,)`` where that axis splits its dim and ``()`` where it has
    size 1 or the mesh lacks it; ``reason`` is None, or names the first
    axis of size > 1 that is not in ``dims`` or does not divide its dim —
    an axis the kernel has no local meaning for."""
    axes = {name: () for name in dims}
    for axis, size in (mesh.shape.items() if mesh is not None else ()):
        if size == 1:
            continue
        if axis not in dims or dims[axis] % size:
            return f"mesh_axis:{axis}={size}", {name: () for name in dims}
        axes[axis] = (axis,)
    return None, axes


def record(kernel, reason=None):
    """Count one trace-time selection for ``kernel``.

    ``reason`` is None when the Pallas kernel is taken, else a short
    string saying why the ``jnp`` form runs instead.  Returns True for
    the kernel, so a call site reads ``if record("x", why): kernel``."""
    impl = "pallas" if reason is None else "jnp"
    telemetry.get_registry().counter(
        "hetu_kernel_choice_total",
        "Trace-time selections between a Pallas kernel and its jnp form",
        labels=("kernel", "impl", "reason"),
    ).labels(kernel=kernel, impl=impl, reason=reason or "").inc()
    if reason is not None and mosaic():
        _log.warning("%s: jnp form on tpu (%s)", kernel, reason)
    return reason is None


def count_inverse(rule, source):
    """Count one traced call of a delta rule's kernel (``rule``: ``gdn`` or
    ``kda``) by where it has its chunks' ``(I + L)^-1`` from: ``solved`` by
    substitution (the forward kernels) or ``kept``, read as the forward
    kernel wrote it (the backward kernels)."""
    telemetry.get_registry().counter(
        "hetu_delta_inverse_total",
        "Trace-time calls of the delta rules' kernels by where a chunk's "
        "triangular inverse comes from: solved by substitution (a forward "
        "kernel) or kept, read as the forward kernel wrote it (a backward "
        "kernel)",
        labels=("rule", "source"),
    ).labels(rule=rule, source=source).inc()


def count_gmm_plan(kernel, k_blocks, row_passes):
    """Count one traced grouped product (``kernel``: ``hetu_moe_gmm_fwd``,
    ``_dx`` or ``_dw``) by its plan (``moe_gmm.gmm_plan`` / ``tgmm_plan``):
    the blocks its contraction (``_dw``: its output's rows) is cut into, 1
    where an expert's weights are fetched once, and the times it walks the
    row tiles, 1 where every row tile is read once."""
    telemetry.get_registry().counter(
        "hetu_moe_gmm_plan_total",
        "Trace-time grouped products by their blocks: k_blocks 1 fetches an "
        "expert's weights once, row_passes 1 reads every row tile once",
        labels=("kernel", "k_blocks", "row_passes"),
    ).labels(kernel=kernel, k_blocks=str(k_blocks),
             row_passes=str(row_passes)).inc()


#: the reason of a kernel that has no per-shard form, under a mesh
MESH = "mesh"

#: kernels behind a function that was ``jax.numpy`` before it had them: on a
#: platform without Mosaic there is no choice, so ``take`` records nothing
#: there unless the caller asked for the kernels.  Any other label says
#: ``platform:<name>``, as the kernels with a plan do.
NO_CHOICE_OFF_TPU = frozenset({"gated_delta", "ssd", "kda", "causal_conv",
                               "gated_norm", "moe_rows", "rotary",
                               "hc_mix", "mla_pack", "moe_select",
                               "qk_norm_rope", "selective_scan"})


def take(kernel, mesh, reason=None, asked=False):
    """Whether ``kernel``'s Pallas form runs, recorded: the one rule of
    every kernel that has no per-shard form.

    ``mesh`` is the mesh the calling node sees, or None (a function that
    cannot see one hands None, and its node asks for it under a mesh: a
    ``pallas_call`` does not partition under GSPMD, so under a mesh the
    ``jax.numpy`` form runs and the reason is ``mesh``).  ``reason`` is the
    kernel file's own ``unsupported(..)``: None, or why its rule refuses
    the operands.  ``asked``: the caller named the form itself, so the
    platform is not a reason (interpret mode where there is no Mosaic)."""
    there = mosaic() or asked
    if not there and kernel in NO_CHOICE_OFF_TPU:
        return False
    if mesh is not None:
        reason = MESH
    elif not there:
        reason = f"platform:{platform()}"
    return record(kernel, reason)


#: what every ``ht.remat()`` group keeps beside its arguments, by kernel: the
#: names its forward rule gives the residuals that are dearest to make again
#: (``named``, INSIDE the ``custom_vjp``'s forward rule: the backward rule
#: reads the residual, so a name on the node's output keeps a copy and still
#: runs the kernel again).  Flash attention's context ``B x S x heads x d`` of
#: the compute type and log-sum-exp ``B x heads x S`` f32 a call.  The window
#: kernels share the rule and the names, which hold no kernel's name (a reader
#: of the device trace finds a kernel by its name anywhere in an event's).
#: The gated delta rule's output ``B x S x heads x d_v`` of the compute type
#: and, f32 a chunk of 64 and head, its chunk-start state ``d_k x d_v`` and
#: the chunk's inverse ``64 x 64`` (a layer of the Qwen3-Next cell: 64 + 268 +
#: 67 MB, 134 for the last in HBM's tiles of 128 lanes; ``hetu_gdn_fwd`` then
#: runs once a layer application).  The other scans' are not in it: their
#: cells have no memory for them (PERF.md section 7)
KEPT = {"flash": ("attention_context", "attention_lse"),
        "gdn": ("delta_rule_output", "delta_rule_states",
                "delta_rule_inverses")}


def named(kernel, *residuals):
    """``residuals`` under ``KEPT[kernel]``'s names, in order; outside a
    ``jax.checkpoint`` a name is the identity and lowers to nothing."""
    return tuple(checkpoint_name(r, name) for r, name
                 in zip(residuals, KEPT[kernel], strict=True))


#: the ``jax.checkpoint`` policy of every recomputed group (``graph/trace.py``):
#: its arguments and every name of ``KEPT``.  ONE object: jax caches a group's
#: partial evaluation by the policy's identity, and a policy made anew a group
#: lowers every jitted function of the group a second time under another name
KEEP_POLICY = jax.checkpoint_policies.save_only_these_names(
    *(name for names in KEPT.values() for name in names))


_group = threading.local()


@contextlib.contextmanager
def keeping(tally):
    """While a recomputed group that has a backward pass is traced:
    ``kept`` appends to ``tally``."""
    before, _group.tally = getattr(_group, "tally", None), tally
    try:
        yield
    finally:
        _group.tally = before


def keeps():
    """Whether a recomputed group that has a backward pass is being traced:
    its policy keeps what ``named`` names."""
    return getattr(_group, "tally", None) is not None


def kept(kernel, nbytes):
    """One kernel call that named ``nbytes`` of residuals: counted where a
    group's policy keeps them, nothing elsewhere."""
    if keeps():
        _group.tally.append((kernel, int(nbytes)))


def record_kept(tally):
    """A traced step's tally into the registry:
    ``hetu_remat_kept_total{kernel}`` once a call, ``hetu_remat_kept_bytes``
    the step's sum."""
    registry = telemetry.get_registry()
    calls = registry.counter(
        "hetu_remat_kept_total",
        "Trace-time kernel calls whose named residuals a recomputed "
        "group's policy keeps for the backward pass", labels=("kernel",))
    for kernel, _ in tally:
        calls.labels(kernel=kernel).inc()
    registry.gauge(
        "hetu_remat_kept_bytes",
        "Bytes of named kernel residuals the recomputed groups of the last "
        "traced step keep").set(sum(n for _, n in tally))


def counted(name):
    """``[(labels, count), ...]`` of a trace-time counter's non-zero
    samples (none while telemetry is disabled, since the registry then
    counts nothing)."""
    metric = telemetry.get_registry().snapshot().get(name, {"samples": []})
    return [(s["labels"], int(s["value"]))
            for s in metric["samples"] if s["value"]]


def choices():
    """``{(kernel, impl, reason): count}`` recorded so far (empty while
    telemetry is disabled)."""
    return {(lab["kernel"], lab["impl"], lab["reason"]): n
            for lab, n in counted("hetu_kernel_choice_total")}
