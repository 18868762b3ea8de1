"""MoE plumbing ops.

Reference kernels: src/ops/{LayoutTransform,H_A2A_LayoutTransform,TopKIdx,
GroupTopKIdx,Scatter1D,SamMax,SamGroupSum,MinDist}.cu and graph ops
gpu_ops/{LayoutTransform,ReverseLayoutTransform,TopKIdx,BalanceAssignment,
Sample,Scatter1D}.py — scatter tokens into (expert, capacity) buffers before
the all-to-all and back after.

TPU redesign: behind a capacity, dispatch and combine are row gathers by the
gate's routing choices (``sparse_dispatch`` / ``sparse_combine``; the
GShard-style one-hot einsums stay for a gate without a choices form and as
the tests' oracle), static shapes either way; capacity overflow drops match
the reference's LayoutTransform semantics; without one, ``dropless_moe``
(below).  The EP all-to-all is inserted by GSPMD from the
expert-dim shardings (layers/moe.py), or composed explicitly with
parallel/collectives.hierarchical_all_to_all for DCN×ICI topologies.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from .base import simple_op
from ..graph.node import named_scope


def top_k_gating(logits, k, capacity, *, second_renorm=True,
                 noise_rng=None, noise_eps=0.0):
    """GShard top-k gating (any ``1 <= k <= E``; ``second_renorm``
    rescales the kept gates of a token to sum to 1 when ``k >= 2``).

    logits: [T, E] raw gate outputs.  Returns (dispatch [T, E, C] float,
    combine [T, E, C] float, aux_loss scalar).  Tokens beyond per-expert
    capacity C are dropped (zero rows), as in the reference TopGate
    (python/hetu/layers/TopGate.py GShard top-2 with capacity).
    """
    choices, aux = top_k_gating_choices(
        logits, k, capacity, second_renorm=second_renorm,
        noise_rng=noise_rng, noise_eps=noise_eps)
    T, E = logits.shape
    dispatch, combine = _accumulate_dispatch(T, E, capacity, choices,
                                             logits.dtype)
    return dispatch, combine, aux


def top_k_gating_choices(logits, k, capacity, *, second_renorm=True,
                         noise_rng=None, noise_eps=0.0):
    """``top_k_gating`` in CHOICES form — [(expert_idx, gate, pos)] per
    routing choice plus the aux loss, never materializing the [T, E, C]
    dispatch/combine tensors (``sparse_dispatch`` and ``sparse_combine``
    gather rows by them)."""
    T, E = logits.shape
    if not 1 <= k <= E:
        raise ValueError(f"top_k_gating needs 1 <= k <= {E} experts, got "
                         f"k={k}")
    probs = jax.nn.softmax(logits, axis=-1)
    if noise_rng is not None and noise_eps > 0:
        logits = logits + noise_eps * jax.random.normal(noise_rng,
                                                        logits.shape)
    masks_gates = []
    remaining = logits
    for _ in range(k):
        mask = jax.nn.one_hot(jnp.argmax(remaining, axis=-1), E,
                              dtype=probs.dtype)
        masks_gates.append((mask, jnp.sum(probs * mask, axis=-1)))
        remaining = jnp.where(mask > 0, -jnp.inf, remaining)

    # load-balancing aux loss (GShard eq.4): E * mean(me * ce), counting
    # each token's first choice
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(masks_gates[0][0], axis=0)
    aux = E * jnp.sum(me * ce)

    choices = _choices_with_positions(masks_gates)
    # zero dropped gates BEFORE renorm so kept mass renormalizes to 1
    choices = [(i, g * (p < capacity), p) for (i, g, p) in choices]
    if k >= 2 and second_renorm:
        total = sum(g for _, g, _ in choices)
        denom = total + 1e-9
        choices = [(i, g / denom * (total > 0), p)
                   for (i, g, p) in choices]
    return choices, aux


def sparse_dispatch(tokens, choices, num_experts, capacity):
    """[E, C, H] expert inputs straight from routing choices (reference
    LayoutTransform.cu) — a row gather by the slot→token inverse map; the
    O(T·E·C) one-hot tensors never exist."""
    T, H = tokens.shape
    S = num_experts * capacity
    slot_tok = jnp.full((S,), T, jnp.int32)     # past the end: an empty slot
    for idx, gate, pos in choices:
        keep = (pos < capacity) & (gate > 0)
        slot = jnp.where(keep,
                         idx.astype(jnp.int32) * capacity
                         + pos.astype(jnp.int32), S)
        slot_tok = slot_tok.at[slot].set(
            jnp.arange(T, dtype=jnp.int32), mode="drop",
            unique_indices=True)
    return _take_rows(tokens, slot_tok).reshape(num_experts, capacity, H)


def sparse_combine(expert_out, choices):
    """[T, H] outputs from [E, C, H] expert results + routing choices
    (reference ReverseLayoutTransform.cu): per choice, gather the token's
    slot row (zeros for a pair that was dropped) and scale by its gate."""
    E, C, H = expert_out.shape
    flat = expert_out.reshape(E * C, H)
    out = None
    for idx, gate, pos in choices:
        keep = (pos < C) & (gate > 0)
        slot = jnp.where(keep,
                         idx.astype(jnp.int32) * C
                         + pos.astype(jnp.int32), E * C)
        term = _take_rows(flat, slot) * gate[:, None].astype(flat.dtype)
        out = term if out is None else out + term
    return out


def top_k_balance_aux(logits):
    """Just the GShard balance loss of ``top_k_gating`` — O(T·E), no
    [T,E,C] dispatch/combine tensors (for aux evaluated in a separate
    program from the MoE op, where CSE can't merge the gating)."""
    T, E = logits.shape
    probs = jax.nn.softmax(logits, axis=-1)
    mask1 = jax.nn.one_hot(jnp.argmax(logits, axis=-1), E,
                           dtype=probs.dtype)
    return E * jnp.sum(jnp.mean(probs, axis=0) * jnp.mean(mask1, axis=0))


def ktop1_balance_aux(logits, k):
    """Just the per-prototype balance loss of ``ktop1_gating``."""
    T, E = logits.shape
    Ep = E // k
    sub = logits.reshape(T, k, Ep)
    probs = jax.nn.softmax(sub, axis=-1)
    aux = 0.0
    for i in range(k):
        mask_local = jax.nn.one_hot(jnp.argmax(sub[:, i], axis=-1), Ep,
                                    dtype=probs.dtype)
        aux = aux + Ep * jnp.sum(jnp.mean(probs[:, i], axis=0)
                                 * jnp.mean(mask_local, axis=0))
    return aux


def sam_balance_aux(logits, num_groups):
    """Just the balance + group-alignment terms of ``sam_gating``."""
    T, E = logits.shape
    Eg = E // num_groups
    probs = jax.nn.softmax(logits, axis=-1)
    gidx = jnp.repeat(jnp.arange(num_groups), Eg)
    gmass = sam_group_sum(probs.T, gidx, num_groups).T
    top_group = jnp.argmax(gmass, axis=-1)
    in_group = gidx[None, :] == top_group[:, None]
    first_mask = jax.nn.one_hot(
        jnp.argmax(jnp.where(in_group, logits, -jnp.inf), axis=-1), E,
        dtype=probs.dtype)
    balance = E * jnp.sum(jnp.mean(probs, axis=0)
                          * jnp.mean(first_mask, axis=0))
    alignment = jnp.mean(1.0 - jnp.max(gmass, axis=-1))
    return balance + alignment


def hash_gating_choices(ids, num_experts, capacity, dtype=jnp.float32):
    """``hash_gating`` in CHOICES form (see top_k_gating_choices)."""
    T = ids.shape[0]
    idx = jnp.mod(ids.astype(jnp.int32), num_experts)
    mask = jax.nn.one_hot(idx, num_experts, dtype=dtype)
    choices = _choices_with_positions([(mask, jnp.ones((T,), dtype))])
    return choices, jnp.asarray(0.0, dtype)


def hash_gating(ids, num_experts, capacity, dtype=jnp.float32):
    """HashGate (reference layers/HashGate.py): expert = id % E, gate = 1."""
    T = ids.shape[0]
    choices, _ = hash_gating_choices(ids, num_experts, capacity, dtype)
    dispatch, _ = _accumulate_dispatch(T, num_experts, capacity, choices,
                                       dtype)
    return dispatch, dispatch, jnp.asarray(0.0, dtype)


layout_transform_op = simple_op(
    lambda x, dispatch: jnp.einsum("tec,th->ech", dispatch, x),
    "layout_transform")
reverse_layout_transform_op = simple_op(
    lambda expert_out, combine: jnp.einsum("ech,tec->th", expert_out,
                                           combine),
    "reverse_layout_transform")
topk_idx_op = simple_op(
    lambda x, k=1: jax.lax.top_k(x, k)[1], "topk_idx")
topk_val_op = simple_op(
    lambda x, k=1: jax.lax.top_k(x, k)[0], "topk_val")
def _scatter1d(x, idx, size=None):
    if size is None:
        raise ValueError("scatter1d_op requires size= (static output length;"
                         " XLA needs static shapes)")
    return jnp.zeros((size,) + x.shape[1:],
                     x.dtype).at[idx.astype(jnp.int32)].set(x)


scatter1d_op = simple_op(_scatter1d, "scatter1d")


def _positions_in_queue(mask):
    """Per-token position within its expert's arrival queue; mask [T, E]."""
    return jnp.sum(jnp.cumsum(mask, axis=0) * mask - mask, axis=-1)


def _choices_with_positions(masks_gates):
    """[(mask [T,E], gate [T])] -> [(expert_idx, gate, pos)] with positions
    drawn from per-expert queues SHARED across choices: a later choice
    queues behind every earlier choice's tokens, so two choices can never
    collide in the same (expert, capacity-slot)."""
    used = None
    out = []
    for mask, gate in masks_gates:
        pos = _positions_in_queue(mask)
        if used is not None:
            pos = pos + jnp.sum(mask * used, axis=-1)
        out.append((jnp.argmax(mask, axis=-1), gate, pos))
        counts = jnp.sum(mask, axis=0, keepdims=True)
        used = counts if used is None else used + counts
    return out


def _accumulate_dispatch(T, E, C, choices, dtype):
    """choices: [(expert_idx [T], gate [T], pos [T])] -> dispatch/combine
    [T, E, C] (zero rows for capacity-dropped tokens)."""
    dispatch = jnp.zeros((T, E, C), dtype=dtype)
    combine = jnp.zeros((T, E, C), dtype=dtype)
    for idx, gate, pos in choices:
        keep = (pos < C).astype(dtype)
        oh = (jax.nn.one_hot(idx, E, dtype=dtype)[:, :, None]
              * jax.nn.one_hot(pos.astype(jnp.int32), C, dtype=dtype)
              [:, None, :])
        oh = oh * keep[:, None, None]
        dispatch = dispatch + oh * (gate > 0).astype(dtype)[:, None, None]
        combine = combine + oh * gate[:, None, None]
    return dispatch, combine


def ktop1_gating_choices(logits, k, capacity):
    """``ktop1_gating`` in CHOICES form (see top_k_gating_choices)."""
    T, E = logits.shape
    assert E % k == 0, "KTop1 needs num_experts divisible by k"
    Ep = E // k
    sub = logits.reshape(T, k, Ep)
    probs = jax.nn.softmax(sub, axis=-1)         # softmax per prototype
    aux = 0.0
    masks_gates = []
    for i in range(k):
        idx_local = jnp.argmax(sub[:, i], axis=-1)
        mask_local = jax.nn.one_hot(idx_local, Ep, dtype=probs.dtype)
        gate = jnp.sum(probs[:, i] * mask_local, axis=-1)
        aux = aux + Ep * jnp.sum(jnp.mean(probs[:, i], axis=0)
                                 * jnp.mean(mask_local, axis=0))
        mask = jax.nn.one_hot(i * Ep + idx_local, E, dtype=probs.dtype)
        masks_gates.append((mask, gate))
    return _choices_with_positions(masks_gates), aux


def ktop1_gating(logits, k, capacity):
    """KTop1 gate (reference layers/KTop1Gate.py): experts split into k
    prototypes of E/k; each token routes top-1 WITHIN every prototype
    (k assignments total), with an independent balance loss per prototype.
    """
    T, E = logits.shape
    choices, aux = ktop1_gating_choices(logits, k, capacity)
    dispatch, combine = _accumulate_dispatch(T, E, capacity, choices,
                                             logits.dtype)
    return dispatch, combine, aux


def sam_gating_choices(logits, k, capacity, num_groups):
    """``sam_gating`` in CHOICES form (see top_k_gating_choices)."""
    T, E = logits.shape
    assert E % num_groups == 0
    Eg = E // num_groups
    assert k <= Eg, (f"SAM routes within one group of {Eg} experts; "
                     f"k={k} would exhaust it")
    probs = jax.nn.softmax(logits, axis=-1)
    gmass = sam_group_sum(probs.T, jnp.repeat(jnp.arange(num_groups), Eg),
                          num_groups).T                    # [T, G]
    top_group = jnp.argmax(gmass, axis=-1)                 # [T]
    in_group = (jnp.repeat(jnp.arange(num_groups), Eg)[None, :]
                == top_group[:, None])
    masked = jnp.where(in_group, logits, -jnp.inf)
    masks_gates = []
    remaining = masked
    first_mask = None
    for _ in range(k):
        idx = jnp.argmax(remaining, axis=-1)
        mask = jax.nn.one_hot(idx, E, dtype=probs.dtype)
        if first_mask is None:
            first_mask = mask
        masks_gates.append((mask, jnp.sum(probs * mask, axis=-1)))
        remaining = jnp.where(mask > 0, -jnp.inf, remaining)
    choices = _choices_with_positions(masks_gates)
    balance = E * jnp.sum(jnp.mean(probs, axis=0)
                          * jnp.mean(first_mask, axis=0))
    alignment = jnp.mean(1.0 - jnp.max(gmass, axis=-1))
    return choices, balance + alignment


def sam_gating(logits, k, capacity, num_groups):
    """SAM gate (reference layers/SAMGate.py): experts form ``num_groups``
    locality groups (one per host in the reference); each token picks the
    group with the largest probability mass, then its top-k experts INSIDE
    that group — keeping all its expert traffic on one host.  Aux = GShard
    balance loss + an alignment term rewarding the chosen group's mass
    (adaptation of SamMax.cu's alignment objective).
    """
    T, E = logits.shape
    choices, aux = sam_gating_choices(logits, k, capacity, num_groups)
    dispatch, combine = _accumulate_dispatch(T, E, capacity, choices,
                                             logits.dtype)
    return dispatch, combine, aux


def base_balance_gating(scores, capacity):
    """BASE-layer gate (reference BalanceGate.py + BalanceAssignment op):
    capacity-constrained assignment balances load exactly; combine weight
    is sigmoid(token · centroid) as in the BASE layer."""
    T, E = scores.shape
    idx = balance_assignment(scores, capacity)
    gate = jax.nn.sigmoid(scores[jnp.arange(T), idx])
    mask = jax.nn.one_hot(idx, E, dtype=scores.dtype)
    pos = _positions_in_queue(mask)
    dispatch, combine = _accumulate_dispatch(
        T, E, capacity, [(idx, gate, pos)], scores.dtype)
    return dispatch, combine, jnp.asarray(0.0, scores.dtype)


def balance_assignment(scores, capacity=None):
    """BASE-layer balanced assignment (reference BalanceAssignment op /
    MinDist.cu auction).  Greedy capacity-constrained approximation with
    static shapes: iterate experts in score order per token.
    scores: [T, E]; returns expert index per token balancing load to T/E."""
    T, E = scores.shape
    cap = capacity or (T + E - 1) // E

    def assign_token(carry, t):
        load, out = carry
        s = scores[t] - jnp.where(load >= cap, jnp.inf, 0.0)
        e = jnp.argmax(s)
        load = load.at[e].add(1)
        out = out.at[t].set(e)
        return (load, out), None

    load0 = jnp.zeros((E,), jnp.int32)
    out0 = jnp.zeros((T,), jnp.int32)
    (_, out), _ = jax.lax.scan(assign_token, (load0, out0), jnp.arange(T))
    return out


def sam_group_sum(x, group_idx, num_groups):
    """SamGroupSum.cu: segment-sum of gate scores per group."""
    return jax.ops.segment_sum(x, group_idx.astype(jnp.int32),
                               num_segments=num_groups)


# -- dropless routing (OLMoE / Mixtral as published) --------------------------
#
# No capacity: every (token, choice) pair is computed.  The pairs are sorted
# by expert and the expert FFNs run as grouped products over the ragged
# groups, so the work is the 8T rows the routing asks for and not the E x C
# rows of a capacity buffer (at the no-drop capacity factor E/k that is E/k
# times the expert work).

def select_k(x, k, mesh=None, asked=False):
    """``jax.lax.top_k(x, k)[1]`` as int32 for ``x [T, E]`` f32: the ``k``
    largest of a row, largest first, ties to the lower index, ``-inf``
    entries last.  By selection where that is cheaper than the full sort of
    every row ``top_k`` lowers to on a TPU, chosen by what the call can see:
    the first maximum at ``k == 1`` (the algorithm, no kernel: nothing is
    recorded); ``hetu_moe_select`` (``ops/pallas/moe_select.py``: ``k``
    masked maxima over a tile in VMEM) on a TPU without a ``mesh``, recorded
    in ``dispatch.choices()`` under ``moe_select``; ``top_k`` itself
    elsewhere (``asked``: the kernel in interpret mode)."""
    from .pallas import dispatch, moe_select
    if k == 1:
        return jnp.argmax(x, axis=-1).astype(jnp.int32)[:, None]
    if dispatch.take("moe_select", mesh,
                     moe_select.unsupported(x.shape[-1], k, x.dtype),
                     asked=asked):
        # the choice carries no gradient: nothing to differentiate through
        return moe_select.select(jax.lax.stop_gradient(x), k)
    return jax.lax.top_k(x, k)[1].astype(jnp.int32)


def _two_largest_sum(x):
    """``jnp.sum(jax.lax.top_k(x, 2)[0], -1)`` by three reductions and no
    sort: the maximum ``m1``, and ``m2``, the maximum once the first
    position of ``m1`` is taken out (``m1`` again where it stands twice)."""
    m1 = jnp.max(x, axis=-1, keepdims=True)
    twice = jnp.sum((x == m1).astype(jnp.int32), axis=-1) > 1
    below = jnp.max(jnp.where(x < m1, x, -jnp.inf), axis=-1)
    m1 = m1[..., 0]
    return m1 + jnp.where(twice, m1, below)


def top_k_route(logits, k, renorm=False, score="softmax", bias=None,
                scale=None, groups=None, mesh=None):
    """``(idx [T, k] int32, gate [T, k] f32, probs [T, E] f32)``: the ``k``
    largest softmax probabilities of each token, largest first, ties to the
    lower expert index; ``renorm`` rescales them to sum to 1 (Mixtral), the
    default uses them as they are (OLMoE's ``norm_topk_prob: false``).
    Everything in f32 whatever the logits' type, so that routing does not
    depend on the compute type.  With a ``bias`` the ``k`` experts are the
    largest of ``p + bias`` and the gates the chosen ``p`` themselves (the
    ZAYA1 router, ``layers/moe.py StateRouter``).

    ``score="sigmoid"`` is the router of DeepSeek-V3 (arXiv:2412.19437) and
    Nemotron-H: the scores are ``s = sigmoid(logits)``, the ``k`` experts are
    the largest of ``s + bias`` (``bias [E]``: a selection bias that balances
    the load and is no part of the weights), the gates are the chosen ``s``
    themselves (renormalised where ``renorm``) times ``scale``, and ``probs``
    is ``s / sum_j s_j``, what the balance loss averages.

    ``groups=(n_group, topk_group)`` is DeepSeek-V3's group-limited selection
    (eq. 16's node-limited routing; Ling 2.0's ``BailingMoeV2``): the experts
    in ``n_group`` groups of neighbours, a group scored by the sum of its two
    largest ``s + bias``, the ``topk_group`` best groups kept (ties to the
    lower group) and the ``k`` experts chosen among theirs alone.  One group
    is the ungrouped router, bit for bit.

    Every choice is ``select_k``'s (``mesh``: the mesh the calling node sees,
    which a ``pallas_call`` does not partition under)."""
    assert score in ("softmax", "sigmoid"), score
    if score == "softmax":
        scores = probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    else:
        scores = jax.nn.sigmoid(logits.astype(jnp.float32))
        probs = scores / jnp.sum(scores, axis=-1, keepdims=True)
    chosen_by = (scores if bias is None
                 else scores + bias.astype(jnp.float32))
    if groups is not None and groups[0] > 1:
        n_group, topk_group = groups
        T, E = chosen_by.shape
        assert E % n_group == 0 and k <= topk_group * (E // n_group), groups
        by_group = chosen_by.reshape(T, n_group, E // n_group)
        best = select_k(_two_largest_sum(by_group), topk_group, mesh)
        kept = jnp.sum(jax.nn.one_hot(best, n_group, dtype=jnp.int32),
                       axis=1) > 0                           # [T, n_group]
        chosen_by = jnp.where(kept[:, :, None], by_group,
                              -jnp.inf).reshape(T, E)
    idx = select_k(chosen_by, k, mesh)
    # the gates by a one-hot product, not top_k's values: its backward
    # pass is then a product too and not a scatter-add into [T, E]
    gate = jnp.sum(jax.nn.one_hot(idx, scores.shape[-1], dtype=scores.dtype)
                   * scores[:, None, :], axis=-1)
    if renorm:
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    if scale is not None:
        gate = gate * scale
    return idx, gate, probs


def expert_load(idx, num_experts):
    """``[E]`` int32: the (token, choice) pairs routed to each expert."""
    return jnp.sum(jax.nn.one_hot(idx.reshape(-1), num_experts,
                                  dtype=jnp.int32), axis=0)


def load_balancing_loss(probs, load, pairs=None):
    """``E * sum_i (n_i / T) * mean_t p_t,i`` with ``n_i`` the (token,
    choice) pairs at expert ``i``: the form of HF
    ``load_balancing_loss_func`` (Switch eq. 4 over top-k counts).  The
    counts carry no gradient.  ``pairs`` (``T k``) divides the counts in
    place of ``T``: ``n_i / (T k)`` is the share of the pairs routed to
    ``i`` (DeepSeek-V3's ``f_i``, eq. 18, which is ``k`` times smaller)."""
    T, E = probs.shape
    frac = jax.lax.stop_gradient(load.astype(jnp.float32)) / (pairs or T)
    return E * jnp.sum(frac * jnp.mean(probs, axis=0))


def router_z_loss(logits):
    """``mean_t logsumexp_i(logits_t,i)^2`` (ST-MoE, Zoph et al. 2022)."""
    z = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    return jnp.mean(z * z)


def held_rows(pairs, num_experts, count):
    """The static bound on the rows ONE PASS lays out of the pairs that land
    on ``count`` held experts of ``num_experts``: twice the mean share of
    ``pairs`` (token, choice) pairs.  Which pairs land on the held experts
    changes from batch to batch, the buffer's size cannot; what a batch
    routes here beyond the bound is computed by further passes over the same
    buffer (``dropless_moe``), so the bound decides how often a second pass
    runs and never whether a pair is computed.  Twice, from a v5e: with 32
    of 512 experts held the fullest layer and step used 0.74-0.80 of it in
    14 runs, and rows for all ``T k`` pairs cost 11% of the step and 1.6 GiB
    (PR 31); with 8 of 128 the loads average out less (at initial weights 1
    share in 10 took over 1.47 times the mean, 1 in 100 over 2.1) and a
    router that learns sent up to 4.4 times the mean for some tens of steps,
    where four times the mean in one pass cost 19 ms of every step (PR 33)."""
    return min(pairs, -(-2 * pairs * count // num_experts))


def grouped_layout(idx, num_experts, tile=None, held=None, rows=None,
                   offset=0):
    """Where each (token, choice) pair sits once the pairs are sorted by
    expert.  Pair ``p = t * k + c``.  Returns a dict:

    * ``load`` ``[E]``: pairs at each expert (the group sizes);
    * ``slot_of_pair`` ``[T k]``: the row of pair ``p``;
    * ``pair_of_slot`` ``[M]``: the pair in row ``s``, ``-1`` for a row of
      padding;
    * with ``tile``: ``tile_expert`` ``[M / tile]`` and ``n_used`` ``[1]``.

    Without ``tile`` the rows are the pairs in expert order, ``M = T k``
    (what ``jax.lax.ragged_dot`` takes).  With ``tile`` each expert's rows
    start on a multiple of ``tile`` and every expert owns at least one row
    tile, so a row tile belongs to one expert (``tile_expert``);
    ``M = T k + E tile`` is the static bound, the tiles from ``n_used`` on
    hold nothing and are assigned to the last expert.  Only sorts, prefix
    sums and gathers: no scatter.

    ``held=(first, count)``: this device holds the experts ``first ..
    first + count - 1`` of ``num_experts`` and lays out only the pairs routed
    to them; ``load`` is ``[count]``.  The layout is a WINDOW of ``M`` rows
    (``rows`` pairs, ``held_rows``, plus the tile padding) onto the rows all
    of those pairs would take, ``total`` of them, starting at row ``offset``
    (a multiple of ``M``; may be traced): a pair outside the window has
    ``slot_of_pair == M``, past the end, and windows at ``0, M, 2 M, ..``
    below ``total`` hold every pair once.  ``kept`` ``[count]`` are the pairs
    of each held expert inside the window, ``elsewhere`` the pairs routed to
    experts held by other devices, ``has_tile`` ``[count]`` (with ``tile``)
    the experts that own a row tile of the window.  (``sorted_pairs`` then
    ``layout_window``: the sorts are the same for every window.)"""
    return layout_window(sorted_pairs(idx, num_experts, held), tile,
                         held=held, rows=rows, offset=offset)


def sorted_pairs(idx, num_experts, held=None):
    """The pairs of ``idx [T, k]`` sorted by expert, what every window of a
    layout starts from: ``flat`` (the expert of pair ``p``; with ``held`` the
    index among the held experts, or their count for an expert held
    elsewhere), ``order`` (the pairs in expert order), ``rank`` (the position
    of pair ``p`` in it), ``load`` and ``start`` (each expert's pairs and
    where they begin)."""
    E = num_experts
    flat = idx.reshape(-1)
    if held is not None:
        first, E = held
        local = flat - first
        flat = jnp.where((local >= 0) & (local < E), local, E)
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    load = expert_load(flat, E)      # one_hot gives an index of E no count
    return {"flat": flat, "order": order,
            "rank": jnp.argsort(order).astype(jnp.int32), "load": load,
            "start": jnp.cumsum(load) - load}


def layout_window(pairs, tile=None, held=None, rows=None, offset=0):
    """``grouped_layout`` from ``sorted_pairs``."""
    flat, order, rank, load, start = (
        pairs[n] for n in ("flat", "order", "rank", "load", "start"))
    P, E = flat.shape[0], load.shape[0]
    mine = flat < E
    assert held is not None or (isinstance(offset, int) and offset == 0)

    def shifted(n, by):
        # the window at row 0 is the layout itself: nothing to add
        return n if isinstance(by, int) and by == 0 else n + by

    def window(first_row, M, slot):
        """``kept`` and ``slot_of_pair`` of the rows ``offset .. offset + M``
        of a layout where expert ``e`` starts at ``first_row[e]`` and pair
        ``p`` sits in row ``slot[p]``."""
        kept = (jnp.clip(offset + M - first_row, 0, load)
                - jnp.clip(offset - first_row, 0, load))
        inside = mine & (slot >= offset) & (slot < offset + M)
        return kept, jnp.where(inside, slot - offset, M).astype(jnp.int32)

    if held is not None and tile is None:
        M = P if rows is None else rows
        kept, slot_of_pair = window(start, M, rank)
        n = shifted(jnp.arange(M, dtype=jnp.int32), offset)
        total = jnp.sum(load)
        return {"load": load, "kept": kept, "elsewhere": P - total,
                "slot_of_pair": slot_of_pair,
                "pair_of_slot": jnp.where(
                    n < total, jnp.take(order, n, mode="fill",
                                        fill_value=-1), -1),
                "rows": M, "total": total}
    if tile is None:
        return {"load": load, "slot_of_pair": rank, "pair_of_slot": order,
                "rows": P}
    tiles = jnp.maximum(-(-load // tile), 1)
    tile_end = jnp.cumsum(tiles)
    pstart = (tile_end - tiles) * tile
    slot_of_pair = pstart[flat] + rank - start[flat]
    M = P + E * tile
    if held is not None:
        M = -(-(P if rows is None else rows) // tile) * tile + E * tile
        kept, slot_of_pair = window(pstart, M, slot_of_pair)
    n_tiles = M // tile
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_end,
                         shifted(jnp.arange(n_tiles, dtype=jnp.int32),
                                 offset // tile),
                         side="right", method="compare_all"),
        E - 1).astype(jnp.int32)
    e_of_slot = jnp.repeat(tile_expert, tile)
    r = shifted(jnp.arange(M, dtype=jnp.int32), offset) - pstart[e_of_slot]
    valid = (r >= 0) & (r < load[e_of_slot])
    pair_of_slot = jnp.where(
        valid, order[jnp.clip(start[e_of_slot] + r, 0, P - 1)], -1)
    lay = {"load": load, "slot_of_pair": slot_of_pair.astype(jnp.int32),
           "pair_of_slot": pair_of_slot.astype(jnp.int32),
           "tile_expert": tile_expert,
           "n_used": tile_end[-1:].astype(jnp.int32), "rows": M}
    if held is not None:
        lay.update(kept=kept, elsewhere=P - jnp.sum(load),
                   n_used=jnp.clip(lay["n_used"] - offset // tile, 0,
                                   n_tiles).astype(jnp.int32),
                   has_tile=(pstart < offset + M) & (tile_end * tile > offset),
                   total=tile_end[-1] * tile)
    return lay


@jax.custom_vjp
def _rows_out(tokens, pair_of_slot, slot_of_pair):
    """``xs[s] = tokens[pair_of_slot[s] // k]`` (zeros where ``-1``).  The
    backward pass is a gather too: ``d tokens[t]`` is the sum of the ``k``
    rows its pairs sit in, never a scatter-add."""
    k = slot_of_pair.shape[0] // tokens.shape[0]
    tok = jnp.where(pair_of_slot >= 0, pair_of_slot // k, tokens.shape[0])
    return jnp.take(tokens, tok, axis=0, mode="fill", fill_value=0)


def _rows_out_fwd(tokens, pair_of_slot, slot_of_pair):
    return (_rows_out(tokens, pair_of_slot, slot_of_pair),
            (slot_of_pair, tokens.shape[0]))


def _rows_out_bwd(res, d_xs):
    slot_of_pair, T = res
    d = jnp.take(d_xs, slot_of_pair, axis=0)
    d = d.reshape(T, -1, d.shape[-1]).astype(jnp.float32).sum(1)
    return d.astype(d_xs.dtype), None, None


_rows_out.defvjp(_rows_out_fwd, _rows_out_bwd)


@jax.custom_vjp
def _rows_back(out, pair_of_slot, slot_of_pair):
    """``pairs[p] = out[slot_of_pair[p]]``; backward ``d out[s] =
    d pairs[pair_of_slot[s]]`` (zeros in rows of padding)."""
    return jnp.take(out, slot_of_pair, axis=0)


def _rows_back_fwd(out, pair_of_slot, slot_of_pair):
    return _rows_back(out, pair_of_slot, slot_of_pair), (pair_of_slot,)


def _rows_back_bwd(res, d_pairs):
    (pair_of_slot,) = res
    src = jnp.where(pair_of_slot >= 0, pair_of_slot, d_pairs.shape[0])
    return (jnp.take(d_pairs, src, axis=0, mode="fill", fill_value=0),
            None, None)


_rows_back.defvjp(_rows_back_fwd, _rows_back_bwd)

# -- a share of the experts: rows <-> tokens without a [T k, H] array ---------
#
# Of the T k pairs of a layer that holds count of E experts, count / E land
# here.  The two gathers above walk all T k pairs (their backward passes
# read k rows a token), which for 32 of 512 experts is sixteen times the
# rows there are.  The held layout goes between tokens and ROWS instead:
# tokens -> rows is a gather of the rows' tokens, rows -> tokens the sum of
# each token's rows (no scatter-add).  Each is the other's backward pass.
#
# The sum has two forms.  ``_sum_rows`` without ``seg`` is a product with
# the [T, M] one-hot matrix of the rows' tokens: 2 T M H operations, nearly
# all of them by zero.  The device runs one operation at a time, so the
# matrix unit is not "idle" for it: on a v5e the two products a layer were
# 20 ms of Qwen3-Next's 274 ms step and 16.5 of Nemotron-H's 268 (ledger,
# PR 35: ``fusion_bf16_8192_2048``, ``fusion_bf16_8192_2688``), more than
# the experts' own products.  It stays as the ``jax.numpy`` form (the CPU, a
# mesh, shapes the kernel does not take) and as the tests' oracle.  With
# ``seg`` (``token_tiles``) the rows are gathered into token order and each
# tile of tokens sums its own rows in ``hetu_moe_rows_sum``
# (ops/pallas/moe_rows.py): the same bf16 rows, f32 sums and one rounding,
# 2 M' tt H operations.  ``rows_impl`` says which runs.

#: tokens of one tile of the segmented sum
ROWS_TOKENS = 512


def _hot(tok, T, dtype):
    """``[T, M]``: 1 where row ``s`` is token ``t``'s; a row of padding
    (``tok == T``) has no 1."""
    return (tok[None, :] == jnp.arange(T, dtype=tok.dtype)[:, None]
            ).astype(dtype)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TokenTiles:
    """The rows of a window in TOKEN order (``token_tiles``)."""
    src: jax.Array
    loc: jax.Array
    tile_group: jax.Array
    n_used: jax.Array
    tt: int = dataclasses.field(metadata={"static": True})


def token_tiles(tok, T, tt, tile):
    """The rows of a window in TOKEN order, for the segmented sum: the layout
    of ``grouped_layout`` with the tile of ``tt`` tokens a row belongs to in
    the expert's place and a row of padding (``tok == T``) as a pair held
    elsewhere.  ``M' = M + (T / tt) tile`` rows: ``src [M']`` the row in
    each, ``loc [1, M']`` which of its tile's tokens that row is (``-1``:
    none, a row of padding: ``src`` names row 0 there and the kernel adds
    it to no token, where a gather that fills zeros in took twice the time
    on a v5e), ``tile_group`` and ``n_used`` as ``tile_expert`` and
    ``n_used`` there.  It depends on ``tok`` alone: one pass computes it
    once for both sums, forward and backward."""
    M, G = tok.shape[0], T // tt
    lay = layout_window(sorted_pairs(tok // tt, None, (0, G)), tile,
                        held=(0, G), rows=M)
    row = lay["pair_of_slot"]
    first = jnp.repeat(lay["tile_expert"], tile) * tt
    src = jnp.maximum(row, 0)
    loc = jnp.where(row >= 0, tok[src] - first, -1)
    return TokenTiles(src, loc[None, :], lay["tile_expert"], lay["n_used"],
                      tt)


def _sum_rows(v, tok, T, seg=None):
    if seg is not None:
        from .pallas.moe_rows import rows_sum
        return rows_sum(jnp.take(v, seg.src, axis=0, mode="clip"), seg.loc,
                        seg.tile_group, seg.n_used, tokens=T, tt=seg.tt)
    full = jax.lax.Precision.HIGHEST if v.dtype == jnp.float32 else None
    return jnp.matmul(_hot(tok, T, v.dtype), v, precision=full,
                      preferred_element_type=jnp.float32).astype(v.dtype)


def _take_rows(x, tok):
    return jnp.take(x, tok, axis=0, mode="fill", fill_value=0)


@jax.custom_vjp
def _tokens_to_rows(tokens, tok, seg):
    """``xs[s] = tokens[tok[s]]`` (zeros where ``tok[s] == T``); ``seg``
    (``token_tiles``) is for the backward pass, the sum of a token's rows."""
    return _take_rows(tokens, tok)


_tokens_to_rows.defvjp(
    lambda tokens, tok, seg: (_take_rows(tokens, tok),
                              (tok, tokens.shape[0], seg)),
    lambda res, d_xs: (_sum_rows(d_xs, *res), None, None))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rows_to_tokens(v, tok, T, seg):
    """``y[t] = sum of v[s] over the rows s of token t``."""
    return _sum_rows(v, tok, T, seg)


_rows_to_tokens.defvjp(
    lambda v, tok, T, seg: (_sum_rows(v, tok, T, seg), tok),
    lambda T, tok, dy: (_take_rows(dy, tok), None, None))


@jax.custom_vjp
def _pairs_to_rows(by_pair, pair_of_slot, slot_of_pair):
    """``by_row[s] = by_pair[pair_of_slot[s]]`` (0 in a row of padding) for
    one number a pair; backward a gather too (a pair without a row reads
    0)."""
    src = jnp.where(pair_of_slot >= 0, pair_of_slot, by_pair.shape[0])
    return _take_rows(by_pair, src)


_pairs_to_rows.defvjp(
    lambda by_pair, pair_of_slot, slot_of_pair: (
        _pairs_to_rows(by_pair, pair_of_slot, slot_of_pair), slot_of_pair),
    lambda slot_of_pair, d: (_take_rows(d, slot_of_pair), None, None))


@jax.custom_vjp
def _grad_where(w, keep):
    """``w`` as it is; its gradient is zero where ``keep [E]`` is false (an
    expert without a row tile inside the held layout's bound: the ``dw``
    kernel never visits it and would leave its block unwritten)."""
    return w


_grad_where.defvjp(
    lambda w, keep: (w, keep),
    lambda keep, g: (jnp.where(keep[:, None, None], g, 0), None))

#: rows of one tile of the Pallas grouped products: with 64 experts the
#: padding is E x tile / 2 rows on average, 12.5% of OLMoE's 65,536 pairs
GMM_TILE = 256
#: and where a share of the experts is held: at 160 pairs an expert under a
#: bound of 320 rows, 128-row tiles gave a 386.1 ms step on a v5e, 256-row
#: tiles 393.3, 8-row tiles 406.9 (PR 31)
HELD_TILE = 128


def grouped_impl(pairs, num_experts, hidden, inter, dtype, mesh=None,
                 impl=None, tile=None):
    """``(impl, tile)`` of the grouped products: ``"pallas"`` (the
    ``hetu_moe_gmm_*`` kernels over tile-aligned groups) or ``"ragged"``
    (``jax.lax.ragged_dot`` over the pairs in expert order), recorded in
    ``dispatch.choices()`` under ``moe_gmm``.  ``impl`` forces one, ``tile``
    the rows of a tile (default: 256 from 256 pairs an expert on, else 8)."""
    from .pallas import dispatch, moe_gmm
    if tile is None:
        tile = GMM_TILE if pairs // num_experts >= GMM_TILE else 8
    why = moe_gmm.unsupported(pairs, hidden, inter, tile, dtype)
    if impl == "ragged":        # the caller's word comes before the mesh's
        mesh, why = None, "caller:impl=ragged"
    return ("pallas", tile) if dispatch.take(
        "moe_gmm", mesh, why, asked=impl is not None) else ("ragged", None)


def rows_impl(how, tile, mesh, tokens, hidden, dtype):
    """The tokens of one tile of the segmented sum that takes a held pass's
    rows to tokens (``token_tiles``, ``hetu_moe_rows_sum``), or ``None`` for
    the one-hot product (``_sum_rows``), from what the grouped products
    themselves are (``how``, ``tile`` of ``grouped_impl``); recorded in
    ``dispatch.choices()`` under ``moe_rows``.  Nothing is recorded on a
    platform without Mosaic unless the caller asked for the kernels
    (``impl="pallas"``: interpret mode): there is no choice to record."""
    from .pallas import dispatch, moe_rows
    tt = ROWS_TOKENS if tokens >= ROWS_TOKENS else 8
    if how == "pallas":
        why = moe_rows.unsupported(tokens, hidden, tt, tile, dtype)
    else:
        why = f"moe_gmm:{how}"  # the products' own reason is moe_gmm's
    return tt if dispatch.take("moe_rows", mesh, why,
                               asked=how == "pallas") else None


def _every_window(one_pass, step, tokens, idx, gate, weights, *, later):
    """``(y, lay)``: the sum of ``one_pass(tokens, idx, gate, weights,
    offset)[0]`` (``idx``: whatever says where the pairs go, integers, no
    gradient) over the windows ``offset = 0, step, 2 step, ..`` below the
    layout's ``total`` rows, and the first window's ``lay`` (arrays alone)
    with ``computed``, the pairs of each expert that all windows held.
    The first window is an ordinary differentiated call.  The others run
    only where a batch routes more pairs to the held experts than one window
    holds, as many as it takes, in a ``while`` loop over the same buffers;
    their backward pass is a loop too and computes each window again, so a
    step keeps nothing of them whatever their number.  ``later=(pass,
    step)``: the windows after the first are that pass's, of its own number
    of rows, at ``offset = step, step + later step, ..`` (the first's again
    but under ``dropless_moe_over_axis``)."""
    def more(total):
        return lambda c: c[0] < total
    first_step = step
    one_later, step = later

    def fwd(tokens, idx, gate, weights):
        y, pull, lay = jax.vjp(
            lambda t, g, w: one_pass(t, idx, g, w, jnp.int32(0)),
            tokens, gate, weights, has_aux=True)

        def add(c):
            y_here, here = one_later(tokens, idx, gate, weights, c[0])
            return c[0] + step, c[1] + y_here, c[2] + here["kept"]
        _, y, computed = jax.lax.while_loop(
            more(lay["total"]), add, (jnp.int32(first_step), y, lay["kept"]))
        return ((y, dict(lay, computed=computed)),
                (pull, tokens, idx, gate, weights, lay["total"]))

    def bwd(res, ct):
        pull, tokens, idx, gate, weights, total = res
        dy = ct[0]

        def add(c):
            _, pull_here = jax.vjp(
                lambda t, g, w: one_later(t, idx, g, w, c[0])[0], tokens,
                gate, weights)
            return c[0] + step, jax.tree_util.tree_map(
                jnp.add, c[1], pull_here(dy))
        _, (d_tokens, d_gate, d_weights) = jax.lax.while_loop(
            more(total), add, (jnp.int32(first_step), pull(dy)))
        return d_tokens, None, d_gate, d_weights

    run = jax.custom_vjp(lambda *a: fwd(*a)[0])
    run.defvjp(fwd, bwd)
    return run(tokens, idx, gate, weights)


def _experts(xs, lay, w_gate, w_up, w_down, how, tile, held):
    """The expert FFNs over the rows ``xs`` of layout ``lay``: gated
    (``silu(x W_gate) * x W_up``) or, with ``w_gate=None``, ``relu(x
    W_up)^2``, then ``W_down``, as grouped products of the form ``how``."""
    if how == "pallas":
        from .pallas.moe_gmm import grouped_matmul

        def product(a, w):
            if held is not None:
                w = _grad_where(w, lay["has_tile"])
            return grouped_matmul(a, w, lay["tile_expert"], lay["n_used"],
                                  tile, w_up.shape[0])
    else:
        def product(a, w):
            return jax.lax.ragged_dot(a, w, lay.get("kept", lay["load"]))
    if w_gate is None:
        act = jnp.square(jax.nn.relu(product(xs, w_up)))
    else:
        act = jax.nn.silu(product(xs, w_gate)) * product(xs, w_up)
    return product(act, w_down)


@functools.partial(jax.jit, static_argnames=("k", "held", "rows", "tile",
                                             "how", "tt"))
def _held_pass(tokens, by_expert, gate, weights, offset, *, k, held, rows,
               tile, how, tt):
    """The held experts' part of ``y`` from the pairs in the window of rows
    at ``offset`` (``layout_window`` of ``by_expert``), and that window's
    counts; ``tt`` (``rows_impl``): the rows go back to tokens by the
    segmented sum over tiles of ``tt`` tokens.  A jitted function: a model's
    expert layers and every pass over their rows, forward and recomputed,
    are one trace."""
    T = tokens.shape[0]
    with named_scope("hetu_moe_dispatch"):
        lay = layout_window(by_expert, tile, held=held, rows=rows,
                            offset=offset)
        tok = jnp.where(lay["pair_of_slot"] >= 0, lay["pair_of_slot"] // k, T)
        seg = None if tt is None else token_tiles(tok, T, tt, tile)
        xs = _tokens_to_rows(tokens, tok, seg)
    with named_scope("hetu_moe_experts"):
        out = _experts(xs, lay, *weights, how, tile, held)
    with named_scope("hetu_moe_combine"):
        g = _pairs_to_rows(gate.reshape(-1), lay["pair_of_slot"],
                           lay["slot_of_pair"])
        weighted = (out.astype(jnp.float32) * g[:, None]).astype(tokens.dtype)
        return _rows_to_tokens(weighted, tok, T, seg), dict(
            {n: lay[n] for n in ("load", "kept", "elsewhere", "total")},
            computed=lay["kept"])


def dropless_moe(tokens, idx, gate, w_gate, w_up, w_down, *, mesh=None,
                 impl=None, held=None, rows=None):
    """``y[t] = sum_c gate[t, c] * W_down,e( silu(W_gate,e x_t) * W_up,e
    x_t )`` with ``e = idx[t, c]``; no pair is dropped.  ``tokens [T, H]``,
    ``idx, gate [T, k]``, weights ``[E, H, F]``, ``[E, H, F]``,
    ``[E, F, H]``.  Returns ``(y [T, H], counts)``, ``counts`` a dict of
    ``load`` and ``computed`` ``[E]``: the pairs routed to each expert and
    those of them computed (the same).  ``w_gate=None`` is an expert that is
    not gated, ``W_down,e relu(W_up,e x_t)^2`` (Nemotron-H's ``relu2``): two
    grouped products a pass where the gated expert runs three.

    ``held=(first, count)``: the weights are those of ``count`` experts of
    the ``num_experts`` that ``idx`` ranges over, and ``y`` is their part of
    the sum alone: what the experts on other devices would add is left out
    (``grouped_layout``).  ``rows`` bounds the pairs ONE pass lays out
    (``held_rows``); what a batch routes here beyond it is computed by
    further passes over the same rows (``_every_window``), so no pair is
    dropped here either.  ``counts`` is over the held experts then
    (``computed``: the pairs all passes held, ``load`` counted) and holds
    the layout's ``elsewhere``, ``total`` and ``kept``, the pairs the first
    pass held (``computed - kept``: the pairs a further pass took), too."""
    if held is not None:
        return _held_moe(tokens, idx, gate, (w_gate, w_up, w_down), held,
                         rows, rows, mesh, impl)
    T, H = tokens.shape
    k = idx.shape[1]
    E, _, F = w_up.shape
    how, tile = grouped_impl(T * k, E, H, F, tokens.dtype, mesh, impl, None)
    with named_scope("hetu_moe_dispatch"):
        lay = grouped_layout(idx, E, tile)
        xs = _rows_out(tokens, lay["pair_of_slot"], lay["slot_of_pair"])
    with named_scope("hetu_moe_experts"):
        out = _experts(xs, lay, w_gate, w_up, w_down, how, tile, None)
    with named_scope("hetu_moe_combine"):
        by_pair = _rows_back(out, lay["pair_of_slot"], lay["slot_of_pair"])
        y = jnp.sum(by_pair.reshape(T, k, H).astype(jnp.float32)
                    * gate[:, :, None], axis=1).astype(tokens.dtype)
    return y, {"load": lay["load"], "computed": lay["load"]}


def _held_moe(tokens, idx, gate, weights, held, rows, later_rows, mesh=None,
              impl=None):
    """``dropless_moe(held=, rows=)`` with ``weights = (w_gate, w_up,
    w_down)``; a pass after the first lays out ``later_rows`` pairs."""
    T, H = tokens.shape
    k = idx.shape[1]
    E, _, F = weights[1].shape
    pairs = rows or T * k
    tile = HELD_TILE if pairs // E >= HELD_TILE else 8
    pairs = -(-pairs // tile) * tile           # the layout rounds up too
    how, tile = grouped_impl(pairs, E, H, F, tokens.dtype, mesh, impl, tile)
    a_pass = functools.partial(
        _held_pass, k=k, held=tuple(held), tile=tile, how=how,
        tt=rows_impl(how, tile, mesh, T, H, tokens.dtype))
    with named_scope("hetu_moe_dispatch"):
        by_expert = sorted_pairs(idx, None, held)   # once for every window
    if rows is None:                   # rows for every pair: one window
        return a_pass(tokens, by_expert, gate, weights, jnp.int32(0),
                      rows=None)

    def window(n):
        """A pass over ``n`` pairs and the rows it lays out
        (``layout_window``'s ``M``)."""
        return (functools.partial(a_pass, rows=n),
                n if tile is None else -(-n // tile) * tile + E * tile)
    return _every_window(*window(rows), tokens, by_expert, gate, weights,
                         later=window(later_rows))


# -- dropless experts over an expert axis --------------------------------------
#
# Inside ``shard_map`` over one mesh axis of ``n`` devices, each holding its own
# tokens and ``E / n`` experts (the weights' dim 0 on the axis).  With ``k``
# choices over ``n`` devices a token has a pair on a given device with
# probability ``1 - (1 - 1/n)^k`` (0.90 at 8 over 4), so an all-to-all of pairs
# would send ``k (n - 1) / n`` rows a token where an all-gather of tokens sends
# ``n - 1``; and what a device then does with everyone's tokens is the held
# pass above with ``first = count x axis_index``.  So: all-gather the tokens,
# their choices and their weights; the held passes (twice the mean share a
# pass, what a batch routes here beyond it by further passes, no pair
# dropped); reduce-scatter the partial sums, each token's to the device it came
# from.  The backward pass is the same pair transposed, by jax's own rules.

#: a pass after a device's first lays out this share of the first's rows:
#: with every device's tokens here the popular experts' device takes 2.1-2.7
#: times the mean share at initial weights (v5e, four seeds: PERF.md, PR 72),
#: what spills over twice the mean is a fraction of a window, and a window
#: costs what it lays out (gathers of its rows whether live or not)
LATER_SHARE = 4


def exchange_bytes(tokens_local, hidden, k, n, itemsize):
    """``{"gather": .., "scatter": ..}``: the bytes ONE device receives in
    the forward pass's all-gather of ``tokens_local`` tokens a device (with
    ``k`` int32 choices and f32 weights each) and in its reduce-scatter of
    the partial sums, over ``n`` devices."""
    rows = (n - 1) * tokens_local
    return {"gather": rows * (hidden * itemsize + 8 * k),
            "scatter": rows * hidden * itemsize}


def exchange_bytes_a_step(forward, forward_passes=1):
    """``forward`` (``exchange_bytes``) over ONE TRAINING STEP of a layer:
    the bytes a device receives in all its all-gathers (``gather``) and in
    all its reduce-scatters (``scatter``).  The backward pass is the forward
    pair transposed: the sums' cotangent is gathered (the scatter's bytes)
    and the tokens' and weights' cotangents are scattered (the choices have
    none: half way between the two).  A layer recomputed in the backward pass
    (``forward_passes`` 2) gathers once more and scatters nothing more, since
    nothing reads the recomputed sums: three all-gathers and two
    reduce-scatters a layer and step on the device's trace (PR 72)."""
    gather, scatter = forward["gather"], forward["scatter"]
    return {"gather": forward_passes * gather + scatter,
            "scatter": scatter + (gather + scatter) // 2}


def dropless_moe_over_axis(tokens, idx, gate, w_gate, w_up, w_down, *, axis,
                           num_experts, impl=None):
    """``dropless_moe`` of THIS device's ``tokens [T, H]`` (``idx, gate [T,
    k]`` over all ``num_experts``) where the experts are spread over the mesh
    axis ``axis``: the weights are this device's ``num_experts / n`` experts,
    the ``axis_index``-th run of them.  Call inside ``shard_map`` over
    ``axis`` (a kernel sees no mesh there: the grouped products, the row sums
    run their Pallas forms on the shard).  Returns ``(y [T, H], counts)``:
    ``load`` and ``computed`` ``[num_experts]`` over ALL devices' tokens, the
    host's counts, the same on every device, and ``later`` ``[num_experts]``,
    the pairs a pass after a device's first computed."""
    n = jax.lax.axis_size(axis)
    count = w_up.shape[0]
    assert count * n == num_experts, (count, n, num_experts)
    with named_scope("hetu_moe_exchange"):
        everyone = [jax.lax.all_gather(a, axis, axis=0, tiled=True)
                    for a in (tokens, idx, gate)]
    tokens_all, idx_all, gate_all = everyone
    # the experts here as 0 .. count - 1; every other index is held elsewhere
    mine = idx_all - count * jax.lax.axis_index(axis).astype(idx_all.dtype)
    rows = held_rows(idx_all.size, num_experts, count)
    part, counts = _held_moe(
        tokens_all, mine, gate_all, (w_gate, w_up, w_down), (0, count), rows,
        rows // LATER_SHARE, impl=impl)
    with named_scope("hetu_moe_exchange"):
        y = jax.lax.psum_scatter(part, axis, scatter_dimension=0, tiled=True)
        later = counts["computed"] - counts["kept"]
        host = {name: jax.lax.all_gather(rows, axis, axis=0, tiled=True)
                for name, rows in (("load", counts["load"]),
                                   ("computed", counts["computed"]),
                                   ("later", later))}
    return y, host
