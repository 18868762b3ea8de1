"""SDAR's block-diffusion training pass through ``SdarMoeForCausalLM`` against
the plain reference (``chipbench/reference/sdar.py``) at a small size on the
CPU: seeded weights with every norm weight moved off its initial value, f32
compute, one chip's share of the experts held, with and without whole layers
recomputed.  And what makes the pass block diffusion's: the noised half's
logits of block ``b`` are what a plain forward pass over ``[clean blocks < b |
noised block b]`` under the block-causal mask gives, for every ``b`` (one pass
computes what generation computes a block at a time); the shares of all the
expert ranks add up to the uncut layer.

Program and reference both compute in f32 here, in different orders (sorted
grouped products against every-expert-masked sums, one softmax against blocked
attention), so they differ by rounding alone.  The negative controls show how
far that is from getting the architecture wrong."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import hetu_tpu as ht
from hetu_tpu.dataloader import block_diffusion_noise
from hetu_tpu.graph.node import graph_variables
from hetu_tpu.models import SdarMoeConfig, SdarMoeForCausalLM

from chipbench.builders.sdar import reference_params
from chipbench.reference import sdar as ref

B, L, K = 2, 48, 4
E, HELD = 16, (4, 4)         # experts 4..7 of 16
MASK = 255
LOGIT_TOL = 2e-4
REF_CONFIG = dict(
    vocab_size=256, hidden_size=48, num_hidden_layers=2,
    num_attention_heads=8, num_key_value_heads=2, head_dim=16,
    rope_theta=1000000, rms_norm_eps=1e-6, num_experts_per_tok=4,
    moe_intermediate_size=24, norm_topk_prob=True)
#: the reference reads the block length under ``assumed``
C = dict(REF_CONFIG, assumed={"block_length": K})
RNG = np.random.default_rng(0)
IDS, LABELS, WEIGHTS = block_diffusion_noise(
    RNG.integers(0, MASK, (B, L)), K, MASK, RNG)


def build(name, remat=None, held=HELD, **over):
    ids = ht.placeholder_op(f"{name}_ids", (B, 2 * L), dtype=np.int32)
    labels = ht.placeholder_op(f"{name}_labels", (B, L), dtype=np.int32)
    weights = ht.placeholder_op(f"{name}_weights", (B, L), dtype=np.float32)
    model = SdarMoeForCausalLM(SdarMoeConfig(
        seq_len=L, block_length=K, mask_token_id=MASK, num_experts=E,
        experts_held=held, remat=remat, **dict(REF_CONFIG, **over)),
        name=name)
    logits = model(ids)
    loss, terms = model.loss_terms(ids, labels, weights, logits=logits)
    variables = graph_variables([loss], trainable_only=True)
    ex = ht.Executor(
        {"forward": [logits, loss, terms["ce_masked"]] + model.moe_loads(),
         "grads": [loss] + ht.gradients(loss, variables)}, seed=3)
    r = np.random.default_rng(7)
    for key, value in list(ex.params.items()):
        if key.endswith("_scale"):
            ex.params[key] = value + jnp.asarray(
                r.normal(0, 0.2, value.shape), value.dtype)
    feed = {ids: IDS, labels: LABELS, weights: WEIGHTS}
    return model, ex, variables, feed


@pytest.fixture(scope="module", params=[None, "layer"],
                ids=["remat_None", "remat_layer"])
def sdar(request):
    model, ex, variables, feed = build(f"sdarref_{request.param}",
                                       request.param)
    out = ex.run("forward", feed_dict=feed, convert_to_numpy_ret_vals=True)
    params = {k: np.asarray(v) for k, v in reference_params(
        model, ex.params).items()}
    sums = jax.jit(lambda p: ref.loss_sums(
        p, C, IDS, LABELS, WEIGHTS, held=HELD, keep_logits=True))(params)
    return dict(model=model, ex=ex, variables=variables, feed=feed, out=out,
                params=params, sums=jax.device_get(sums))


def test_layers_and_weights(sdar):
    layers = sdar["model"].model.layers
    assert [l.attn.block_diffusion for l in layers] == [K, K]
    assert all(l.attn.qk_norm_per_head and not l.attn.causal for l in layers)
    assert all(l.mlp.held == HELD and l.mlp.shared is None for l in layers)
    want = len(ref.WEIGHTS) + 2 * len(ref.LAYER_WEIGHTS)
    assert len(sdar["params"]) == want == len(sdar["variables"])


def test_logits_and_loss_terms_match_reference(sdar):
    want = sdar["sums"]["logits"]
    assert want.shape == (B * L, REF_CONFIG["vocab_size"])
    assert np.abs(want).max() > 0.3
    assert np.abs(sdar["out"][0] - want).max() < LOGIT_TOL
    terms = ref.loss_from_sums(sdar["sums"])
    for got, term in zip(sdar["out"][1:3], ("loss", "ce_masked")):
        assert abs(float(got) - float(terms[term])) < 1e-5 * float(
            terms[term]), term
    # the weighted loss is not the plain mean: about 1 / t a position
    assert abs(float(terms["loss"]) - float(terms["ce_masked"])) > 0.1


def test_every_gradient_leaf_matches_reference(sdar):
    ex, variables = sdar["ex"], sdar["variables"]
    got = ex.run("grads", feed_dict=sdar["feed"],
                 convert_to_numpy_ret_vals=True)[1:]
    want = jax.jit(jax.grad(lambda p: ref.training_loss(
        p, C, IDS, LABELS, WEIGHTS, held=HELD)))(sdar["params"])
    names = {v: k for k, v in reference_params(
        sdar["model"], {n: n for n in ex.params}).items()}
    for var, g in zip(variables, got):
        w = np.asarray(want[names[var.name]])
        assert np.abs(w).max() > 0, var.name
        assert np.abs(g - w).max() < 5e-4 * np.abs(w).max() + 1e-9, var.name


def test_load_vector_is_the_references(sdar):
    first, count = HELD
    for load, ch in zip(sdar["out"][3:], sdar["sums"]["chosen"]):
        assert ch.shape == (B * 2 * L, REF_CONFIG["num_experts_per_tok"])
        theirs = np.bincount(ch.reshape(-1), minlength=E)
        np.testing.assert_array_equal(load[0], theirs[first:first + count])
        np.testing.assert_array_equal(load[1], load[0])     # none dropped


# -- the two-copy pass is what generation computes ---------------------------

def block_by_block(params, b, held=HELD):
    """Logits ``[B, K, V]`` of noised block ``b`` from a plain forward pass
    over ``[clean blocks < b | noised block b]`` at positions ``0 .. (b + 1) K
    - 1`` under the block-CAUSAL mask (a key's block not after the query's):
    what a generation step that denoises block ``b`` computes."""
    n = (b + 1) * K
    ids = np.concatenate([IDS[:, :b * K], IDS[:, L + b * K:L + n]], axis=1)
    p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    eps = C["rms_norm_eps"]
    H, kv, d = (C["num_attention_heads"], C["num_key_value_heads"],
                C["head_dim"])
    at = jnp.arange(n)
    seen = (at[None, :] // K) <= (at[:, None] // K)
    with jax.default_matmul_precision("highest"):
        x = p["embed"][ids]
        for l in range(C["num_hidden_layers"]):
            w = {k[len(f"layers.{l}."):]: v for k, v in p.items()
                 if k.startswith(f"layers.{l}.")}
            u = ref._norm(x, w["input_norm"], eps)
            q = ref._norm((u @ w["q"]).reshape(B, n, H, d), w["q_norm"], eps)
            k = ref._norm((u @ w["k"]).reshape(B, n, kv, d), w["k_norm"], eps)
            v = (u @ w["v"]).reshape(B, n, kv, d)
            q, k = (ref.rotate(t, at, C["rope_theta"]) for t in (q, k))
            reads = jnp.arange(H) // (H // kv)
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k[:, :, reads]) / d ** 0.5
            prob = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
            o = jnp.einsum("bhqk,bkhd->bqhd", prob, v[:, :, reads])
            x = x + o.reshape(B, n, H * d) @ w["o"]
            h = ref._norm(x, w["post_norm"], eps).reshape(B * n, -1)
            y, _ = ref.expert_block(h, w, C, jnp.matmul, held)
            x = x + y.reshape(B, n, -1)
        x = ref._norm(x[:, b * K:], p["norm"], eps)
        return x @ p["lm_head"]


@pytest.mark.parametrize("b", range(L // K))
def test_the_noised_half_is_a_forward_pass_block_by_block(sdar, b):
    """For every block ``b``: the program's logits at the noised positions of
    block ``b``, from ONE pass over both copies, equal a plain block-causal
    forward pass over the clean blocks before it and the noised block."""
    got = sdar["out"][0].reshape(B, L, -1)[:, b * K:(b + 1) * K]
    want = np.asarray(jax.jit(block_by_block, static_argnums=1)(
        sdar["params"], b))
    assert np.abs(want).max() > 0.3
    assert np.abs(got - want).max() < LOGIT_TOL


# -- the shares add up --------------------------------------------------------

def test_the_shares_of_all_expert_ranks_add_up_to_the_uncut_layer():
    """Four ranks of four experts: each rank's expert sublayer output (layer
    0, the same weights, router over all 16), summed, is the uncut layer's."""
    model, ex, _, _ = build("sdarshare_whole", held=None)
    params = {k: np.asarray(v) for k, v in reference_params(
        model, ex.params).items()}
    h = jnp.asarray(np.random.default_rng(3).normal(
        size=(B * 2 * L, REF_CONFIG["hidden_size"])), jnp.float32)
    w = {k[len("layers.0."):]: jnp.asarray(v) for k, v in params.items()
         if k.startswith("layers.0.")}
    whole, chosen = ref.expert_block(h, w, C, jnp.matmul, None)
    total = 0.0
    for first in range(0, E, 4):
        share = dict(w, **{k: w[k][first:first + 4]
                           for k in ("w_gate", "w_up", "w_down")})
        y, ch = ref.expert_block(h, share, C, jnp.matmul, (first, 4))
        np.testing.assert_array_equal(np.asarray(ch), np.asarray(chosen))
        total = total + y
    assert np.abs(np.asarray(whole)).max() > 1e-3
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("first", range(0, E, 4))
def test_a_rank_of_the_program_is_the_references_share(first):
    """The program with the experts ``first .. first + 3`` held against the
    reference given the same share: logits within rounding."""
    model, ex, _, feed = build(f"sdarrank_{first}", held=(first, 4))
    got = ex.run("forward", feed_dict=feed, convert_to_numpy_ret_vals=True)[0]
    params = {k: np.asarray(v) for k, v in reference_params(
        model, ex.params).items()}
    want = np.asarray(jax.jit(lambda p: ref.forward(
        p, C, IDS, held=(first, 4))[0])(params))
    assert np.abs(got - want).max() < LOGIT_TOL


# -- negative controls --------------------------------------------------------

@pytest.mark.parametrize("control", [c for c in ref.CONTROLS
                                     if c != "weights"])
def test_a_changed_piece_moves_the_logits(sdar, control):
    got = np.asarray(jax.jit(lambda p: ref.forward(
        p, C, IDS, held=HELD, without=(control,))[0])(sdar["params"]))
    assert np.abs(got - sdar["sums"]["logits"]).max() > 50 * LOGIT_TOL


def test_the_loss_without_its_weights_is_another_loss(sdar):
    plain = jax.jit(lambda p: ref.loss_from_sums(ref.loss_sums(
        p, C, IDS, LABELS, WEIGHTS, held=HELD, without=("weights",))))(
            sdar["params"])
    assert abs(float(plain["loss"]) - float(sdar["out"][1])) > 0.1
    assert abs(float(plain["ce_masked"]) - float(sdar["out"][2])) < 1e-4
