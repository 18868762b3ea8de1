"""Operations and bytes a call needs, from its shapes alone.

These are the algorithm's requirements, not the compiler's count: XLA's
cost analysis counts an implementation's recomputation and sees a Pallas
call as opaque.  A
matrix product of ``[m, k] @ [k, n]`` is ``2 m k n`` operations.
"""

from __future__ import annotations


def bert_train_flops_per_token(c, seq, masked_fraction):
    """Forward plus backward of BERT pretraining, per input token.

    Per layer and token: four ``H x H`` projections (``8 H^2``), the
    feed-forward pair (``4 H I``) and attention's two products against
    ``seq`` keys (``4 seq H``).  The MLM head (transform ``2 H^2`` and the
    tied decoder ``2 H V``) is needed at masked positions only.  Backward is
    twice forward; nothing recomputed is counted."""
    h, i = c["hidden_size"], c["intermediate_size"]
    layer = 8 * h * h + 4 * h * i + 4 * seq * h
    head = masked_fraction * (2 * h * h + 2 * h * c["vocab_size"])
    return 3.0 * (c["num_hidden_layers"] * layer + head)


#: flash attention by passes, each with the part of a device event's name
#: that marks the pass (however many kernels carry it out, under whatever
#: suffix) and the matrix products the algorithm needs for it (Dao et al.
#: 2022): forward QK^T and PV; backward S once more (P is never stored), dP,
#: dV, dK and dQ.  A backward cut into two kernels that each form S and dP
#: again runs 3 + 4 = 7 products; the second S and dP are that cut's
#: recomputation and are not credited, so a backward fused into one kernel
#: reads higher, not lower.
FLASH_PASSES = {
    "forward": {"events": "hetu_flash_fwd", "products": 2,
                "tensors": 4},       # q, k, v read; o written
    "backward": {"events": "hetu_flash_bwd", "products": 5,
                 "tensors": 8}}      # q, k, v, o, do read; dq, dk, dv written


def flash_passes_a_step(want):
    """The flash passes of each kind a step REQUIRES, one a layer
    application, from a builder's ``expected_kernel_shapes()``:
    ``attention_passes`` where the builder states it beside the most forward
    calls a step may make (``attention_layers``: a recomputed layer's
    twice), else ``attention_layers`` (nothing recomputed: one number)."""
    return want.get("attention_passes", want["attention_layers"])


def flash_pass(name, rows, seq, head_dim, itemsize=2):
    """``(operations, bytes)`` of one flash-attention pass over ``rows``
    (batch x heads) sequences, each tensor read or written once."""
    p = FLASH_PASSES[name]
    return (p["products"] * 2.0 * rows * seq * seq * head_dim,
            float(p["tensors"] * rows * seq * head_dim * itemsize))


def softmax_ce_call(kernel, rows, vocab, itemsize=2):
    """``(operations, bytes)`` of one fused softmax-cross-entropy call.
    Forward reads the logits once; backward reads them and writes their
    gradient.  About five operations an element (max, subtract, exp, sum,
    scale)."""
    passes = {"hetu_softmax_ce_fwd": 1, "hetu_softmax_ce_bwd": 2}[kernel]
    return 5.0 * rows * vocab, float(passes * rows * vocab * itemsize)


def roofline_seconds(ops, nbytes, peaks):
    """The least time the chip could take, and which limit sets it."""
    t_ops = ops / peaks["bf16_flops_per_s"]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "hbm")
