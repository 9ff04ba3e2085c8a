"""OLMoE-style routed decoder LM: how the benchmark builds it through the
program, its plain reference (logits, and loss with gradients for the CPU
tests), and its operation counts.

Sizes come from the configuration's JSON (the key names of the model's
``config.json``). A later configuration of the same family adds a JSON
that names this builder; nothing here knows a cell.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# At the top, not in the functions: a program without this architecture
# (the parent of the PR that brought it) fails when the cell is loaded,
# before it starts a cluster or takes the chip.
from raydp_tpu.models.transformer import CausalLM, olmoe

# Program logits (bf16 trunk through the block, float32 router, float32
# head of width ``hidden_size``) against the float32 "highest" reference on
# ALL 4,096 positions of one seeded sequence, as the largest absolute
# difference over the largest reference magnitude (``harness.
# check_reference``), on the state the run's training left.
#
# What sets the error is routing: a token whose 8th and 9th router
# probabilities lie within a bf16 rounding of the trunk gets one expert of
# its eight swapped, and its logits move by several percent of the largest
# while every other position agrees to 0.5% (99.9th percentile). The plain
# reference with its trunk rounded to bfloat16 reads the same as the
# program in all 17 states compared on the chip (3.07 against 2.98%, 6.82
# against 6.89%, ...): the program computes what a bf16 implementation
# computes. Measured on the chip (PERF.md section 6, PR 26), program against
# the float32 reference at the configuration's learning rate: 3.0% after 8
# epochs, and 5.3-12.2% on the state a 30 s run leaves (eight runs, seven
# seeds; 0.7% at init, where the experts barely reach the logits).
# Departures on such states: the top-k probabilities renormalised
# 32.1-70.3%, QK-norm left out 54.1-55.2%, a trunk in float8_e4m3 (the
# precision below the stated one) 56.8-57.9%. 25% is twice the worst run
# (under the four times allowed) and under every departure.
TOLERANCE = 0.25
CHECK_ROWS = 1
AUX_LOSS_WEIGHT = 1e-2
Z_LOSS_WEIGHT = 1e-3
# The reference's experts run this many at a time: [T, 8, F] float32 blocks
# beside 10 GB of training state, not [T, 64, F].
EXPERTS_AT_ONCE = 8


def model_config(sizes: dict):
    if sizes["num_key_value_heads"] != sizes["num_attention_heads"]:
        raise ValueError("grouped-query attention is not in the program")
    if sizes["norm_topk_prob"] or sizes["tie_word_embeddings"]:
        raise ValueError("the program uses the top-k probabilities as "
                         "they are, and a head of its own")
    return olmoe(
        vocab_size=sizes["vocab_size"],
        d_model=sizes["hidden_size"],
        n_heads=sizes["num_attention_heads"],
        n_layers=sizes["num_hidden_layers"],
        max_len=sizes["max_position_embeddings"],
        norm_eps=sizes["rms_norm_eps"],
        rope_theta=float(sizes["rope_theta"]),
        n_experts=sizes["num_experts"],
        top_k=sizes["num_experts_per_tok"],
        d_expert=sizes["intermediate_size"],
        attention_impl=sizes["attention_impl"],
        remat=sizes.get("remat", False),
        dtype=jnp.dtype(sizes["compute_dtype"]),
        param_dtype=jnp.dtype(sizes["param_dtype"]),
    )


def estimator_kwargs(sizes: dict, traffic: dict, mesh_spec) -> dict:
    """Arguments of ``JAXEstimator`` for this configuration."""
    import optax

    opt = sizes["optimizer"]
    return dict(
        model=CausalLM(model_config(sizes)),
        optimizer=getattr(optax, opt["name"])(opt["learning_rate"]),
        loss="lm_ce",
        self_supervised=True,
        aux_losses=True,
        feature_columns=[f"t{i}" for i in range(traffic["seq_len"])],
        label_column=None,
        feature_dtype=np.int32,
    )


def check_batch(sizes: dict, traffic: dict, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.integers(
        0, sizes["vocab_size"], size=(CHECK_ROWS, traffic["seq_len"])
    ).astype(np.int32)


# ------------------------------------------------------ plain reference

def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """``x`` [B, S, H, D]; feature i pairs with i + D/2 (the published
    ``rotate_half``)."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-np.arange(half, dtype=np.float32) / half)
    angle = np.arange(x.shape[1], dtype=np.float32)[:, None] * inv_freq
    cos = jnp.asarray(np.cos(angle))[None, :, None, :]
    sin = jnp.asarray(np.sin(angle))[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _forward(params, ids, sizes: dict, trunk=None):
    """Logits and the summed auxiliary loss of the published block
    (Muennighoff et al. 2024), straightforward float32 ``jax.numpy`` on
    the program's parameter tree: every token through ALL experts,
    multiplied by a top-k mask of the router's probabilities (no sort, no
    grouped matmul, no kernel); dense causal softmax attention; rotary
    positions and norms written out. ``trunk`` is None for the reference;
    a dtype rounds the blocks' weights and every matmul's inputs to it
    (router and head stay float32, as the configuration states), which
    shows what the tolerance refuses. Departure from the published code,
    the program's: q, k and v come from one fused projection (the same
    mathematics)."""
    p = params["params"]
    enc = p["encoder"]
    eps, theta = sizes["rms_norm_eps"], float(sizes["rope_theta"])
    top_k, n_exp = sizes["num_experts_per_tok"], sizes["num_experts"]
    if trunk is None:
        r = lambda a: a  # noqa: E731
    else:
        r = lambda a: a.astype(trunk).astype(jnp.float32)  # noqa: E731
    aux = jnp.zeros((), jnp.float32)
    x = r(enc["tok_embed"]["embedding"])[ids]
    b, s, d = x.shape
    causal = np.tril(np.ones((s, s), bool))
    for i in range(sizes["num_hidden_layers"]):
        blk = enc[f"block_{i}"]
        att = blk["attn"]
        y = r(_rms_norm(x, blk["ln_attn"]["scale"], eps))
        qkv = jnp.einsum("bsd,dthk->bsthk", y, r(att["qkv"]["kernel"]))
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        heads = q.shape[2:]
        q = _rms_norm(q.reshape(b, s, d), att["q_norm"]["scale"], eps)
        k = _rms_norm(k.reshape(b, s, d), att["k_norm"]["scale"], eps)
        q = r(_rope(q.reshape(b, s, *heads), theta))
        k = r(_rope(k.reshape(b, s, *heads), theta))
        scores = jnp.einsum("bqhk,bshk->bhqs", q, k) / np.sqrt(heads[-1])
        scores = jnp.where(causal, scores, -jnp.inf)
        probs = r(jax.nn.softmax(scores, axis=-1))
        ctx = r(jnp.einsum("bhqs,bshk->bqhk", probs, r(v)))
        x = x + jnp.einsum("bqhk,hkd->bqd", ctx, r(att["out"]["kernel"]))

        moe = blk["moe"]
        y = _rms_norm(x, blk["ln_mlp"]["scale"], eps).reshape(b * s, d)
        logits = y @ moe["router"]["kernel"]
        router = jax.nn.softmax(logits, axis=-1)
        # The k largest; equal probabilities go to the lower index.
        by_size = jnp.argsort(-router, axis=-1, stable=True)
        mask = jnp.argsort(by_size, axis=-1) < top_k
        weights = jnp.where(mask, router, 0.0)
        if sizes["norm_topk_prob"]:               # false as published
            weights = weights / weights.sum(axis=-1, keepdims=True)
        y, y_out = r(y), jnp.zeros((b * s, d), jnp.float32)
        for e0 in range(0, n_exp, EXPERTS_AT_ONCE):
            part = slice(e0, e0 + EXPERTS_AT_ONCE)
            h = jax.nn.silu(
                jnp.einsum("td,edf->tef", y, r(moe["w_gate"][part]))
            ) * jnp.einsum("td,edf->tef", y, r(moe["w_up"][part]))
            out = jnp.einsum("tef,efd->ted", r(h), r(moe["w_down"][part]))
            y_out = y_out + jnp.einsum("ted,te->td", out, weights[:, part])
        x = x + y_out.reshape(b, s, d)
        share = mask.astype(jnp.float32).sum(0) / (b * s)
        aux = aux + AUX_LOSS_WEIGHT * n_exp * jnp.sum(
            share * router.mean(0)
        ) + Z_LOSS_WEIGHT * jnp.mean(
            jax.nn.logsumexp(logits, axis=-1) ** 2
        )
    x = _rms_norm(x, enc["ln_final"]["scale"], eps)
    return x @ p["lm_head"]["kernel"], aux


def reference_logits(params, ids, sizes: dict, trunk=None):
    with jax.default_matmul_precision("highest"):
        return _forward(params, ids, sizes, trunk)[0]


def reference_loss_and_grads(params, ids, sizes: dict):
    """Next-token cross-entropy plus both auxiliary terms, and its
    gradients with respect to ``params`` (the CPU tests compare the
    program's against them)."""
    def loss(p):
        logits, aux = _forward(p, ids, sizes)
        logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
        nll = -jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)
        return jnp.mean(nll) + aux

    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss)(params)


# ------------------------------------------------------ operation counts

def n_params(sizes: dict) -> int:
    d, f, e = (sizes["hidden_size"], sizes["intermediate_size"],
               sizes["num_experts"])
    block = 4 * d * d + 2 * d + 2 * d + d * e + 3 * e * d * f
    return (2 * sizes["vocab_size"] * d
            + sizes["num_hidden_layers"] * block + d)


def moe_flops_per_step(sizes: dict, traffic: dict, batch: int) -> float:
    """Operations of the grouped matmuls of one step, forward and
    backward: the ``T·k`` rows that exist, three ``[D, F]`` matrices a
    row, 2 operations a multiply-add, 3 passes (forward, input gradient,
    weight gradient)."""
    rows = batch * traffic["seq_len"] * sizes["num_experts_per_tok"]
    per_row = 3 * 2 * sizes["hidden_size"] * sizes["intermediate_size"]
    return 3.0 * sizes["num_hidden_layers"] * rows * per_row


def attention_flops_per_step(sizes: dict, traffic: dict, batch: int) -> float:
    """Operations of causal attention's kernels in one step: the pairs
    that exist, ``S(S+1)/2`` a head, ``2 × 2 × head_dim`` operations a
    pair forward (scores and mixing), and 2.5 times that backward (the
    blockwise backward recomputes the scores: 5 matmuls for 2)."""
    s, d = traffic["seq_len"], sizes["hidden_size"]
    pairs = s * (s + 1) / 2
    forward = 4.0 * pairs * d
    return sizes["num_hidden_layers"] * batch * forward * 3.5


def flops_per_sample(sizes: dict, traffic: dict) -> float:
    """Operations the forward and backward passes need for one sequence:
    3 x (2 x matrix parameters a token touches x tokens + causal
    attention's scores and mixing over the pairs that exist). A token
    touches the attention projections, the router, ``num_experts_per_tok``
    experts and the head; the embedding lookup is a gather, norms and
    rotary positions are not matmuls, nothing recomputed is counted."""
    s, d = traffic["seq_len"], sizes["hidden_size"]
    per_token = sizes["num_hidden_layers"] * (
        4 * d * d + d * sizes["num_experts"]
        + sizes["num_experts_per_tok"] * 3 * d * sizes["intermediate_size"]
    ) + d * sizes["vocab_size"]
    attention = sizes["num_hidden_layers"] * 4 * d * s * (s + 1) / 2
    return 3.0 * (2 * per_token * s + attention)


def bytes_per_step(sizes: dict, traffic: dict, batch: int) -> float:
    """Bytes one chip's step has to move whatever the schedule: every
    parameter, its gradient and both AdamW moments read and written once
    in float32, and the batch read. Activations are left out, so this is
    a lower bound."""
    return 8.0 * 4 * n_params(sizes) + 4.0 * batch * traffic["seq_len"]
