"""LFM2-MoE style hybrid decoder LM (gated short convolutions beside
grouped-query attention, dense FFNs in the leading layers and routed
experts in the others) as ONE CHIP'S SHARE of an expert-parallel
deployment: how the benchmark builds it through the program, its plain
reference given the same share (logits, and loss with gradients for the
CPU tests), and its operation counts.

Sizes come from the configuration's JSON (the key names of the model's
``config.json``, ``model_type`` lfm2_moe). ``num_experts`` is how many
experts this chip HOLDS; ``num_experts_routed`` is the router's width and
``first_expert`` the first held one. A later configuration of the same
family adds a JSON that names this builder; nothing here knows a cell.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# At the top, not in the functions: a program without this architecture
# (the parent of the PR that brought it) fails when the cell is loaded,
# before it starts a cluster or takes the chip.
from raydp_tpu.models.transformer import CausalLM, lfm2_8b_a1b

# Program logits (bf16 trunk, float32 router, scores, norms and tied head)
# against the float32 "highest" reference GIVEN THE SAME SHARE on ALL 8,192
# positions of one seeded sequence, as the largest absolute difference over
# the largest reference magnitude (``harness.check_reference``), on the
# state the run's training left.
#
# What sets the error is the bf16 trunk itself, not routing flips: every
# position is off by 1.4-1.6% of the largest logit at the median (99.9th
# percentile 2.2-2.5%), and the plain reference with its trunk rounded to
# bfloat16 reads the same as the program (2.1-2.9%). Nothing damps the
# rounding of 14 residual adds here (Granite's branches enter at 0.22 and
# its check reads 0.2-0.3%). Measured on the chip at the published widths
# (PERF.md section 6, PR 32), after a 30 s run under the configuration's
# optimizer, thirteen runs over five seeds: 2.66-3.47%. Departures on such
# states, four seeds: sigmoid scores replaced by softmax 5.29-5.67%, the
# absent experts' parts added back 7.2-8.5%, gates not normalised 9.3-12.2%
# (two seeds), no per-head QK-norm 19.1-19.9%, a trunk in float8_e4m3 (the
# precision below the stated one) 54-58%, the convolution without either
# gate or with two taps 99-139%. 4.4% is 1.27 times the worst run and under
# the smallest of those.
#
# ``no_expert_bias`` reads 3.1-3.3%: the check CANNOT tell it from the
# program's own rounding. The bias that balances a fresh router's loads is
# small (a few hundredths against a score spread of 0.27) and moves one
# expert in four for a minority of tokens; the CPU tests pin it in float32
# (23% at the tiny size, program 1e-6).
TOLERANCE = 0.044
UNSEEN_ON_THE_CHIP = ("no_expert_bias",)
CHECK_ROWS = 1
# The reference's experts run this many at a time: [T, 2, F] float32 blocks
# beside 10.7 GB of training state.
EXPERTS_AT_ONCE = 2

# Changes to the mathematics that ``_forward`` can make on request
# (``depart=``). The tests show that each reads above ``TOLERANCE`` at the
# tiny size in float32, PERF.md that each but ``UNSEEN_ON_THE_CHIP`` does
# at the published widths.
DEPARTURES = (
    "no_expert_bias",        # the k largest scores, not scores + bias
    "softmax_scores",        # softmax over the experts in place of sigmoid
    "gates_not_normalised",  # the selected scores as they are
    "no_head_qk_norm",       # q and k straight into the rotation
    "conv_no_b_gate",        # conv(x), not conv(B * x)
    "conv_no_c_gate",        # W_out z, not W_out (C * z)
    "conv_two_taps",         # the oldest of the three taps dropped
    "uncut_layer",           # the absent experts' parts added back
)


def model_config(sizes: dict):
    if (sizes["conv_bias"] or not sizes["norm_topk_prob"]
            or not sizes["use_expert_bias"]
            or sizes["model_type"] != "lfm2_moe"):
        raise ValueError("not the block this builder writes down")
    if len(sizes["layer_types"]) != sizes["num_hidden_layers"]:
        raise ValueError("layer_types does not name every layer")
    mixers = {"conv": "conv", "full_attention": "attention"}
    return lfm2_8b_a1b(
        vocab_size=sizes["vocab_size"],
        d_model=sizes["hidden_size"],
        n_heads=sizes["num_attention_heads"],
        n_kv_heads=sizes["num_key_value_heads"],
        n_layers=sizes["num_hidden_layers"],
        layer_types=tuple(
            mixers[kind] + (
                ":swiglu" if i < sizes["num_dense_layers"] else ":moe"
            ) for i, kind in enumerate(sizes["layer_types"])
        ),
        d_ff=sizes["intermediate_size"],
        max_len=sizes["max_position_embeddings"],
        norm_eps=sizes["norm_eps"],
        rope_theta=float(sizes["rope_theta"]),
        conv_taps=sizes["conv_L_cache"],
        n_experts=sizes["num_experts_routed"],
        experts_held=sizes["num_experts"],
        first_expert=sizes["first_expert"],
        top_k=sizes["num_experts_per_tok"],
        d_expert=sizes["moe_intermediate_size"],
        routed_scaling=float(sizes["routed_scaling_factor"]),
        attention_impl=sizes["attention_impl"],
        remat=sizes.get("remat", False),
        dtype=jnp.dtype(sizes["compute_dtype"]),
        param_dtype=jnp.dtype(sizes["param_dtype"]),
    )


def _optimizer(opt: dict):
    """``optax.<name>`` at the configuration's rate, reached by a linear
    warm-up from 0 over ``warmup_steps`` steps where the file gives them."""
    import optax

    rate = opt["learning_rate"]
    if opt.get("warmup_steps"):
        rate = optax.linear_schedule(0.0, rate, opt["warmup_steps"])
    return getattr(optax, opt["name"])(rate)


def estimator_kwargs(sizes: dict, traffic: dict, mesh_spec) -> dict:
    """Arguments of ``JAXEstimator`` for this configuration. ``aux_losses``
    is on for the routing counts the step sows (both loss weights are 0:
    the configuration has no auxiliary loss)."""
    return dict(
        model=CausalLM(model_config(sizes)),
        optimizer=_optimizer(sizes["optimizer"]),
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
    """``x`` [B, S, D] of one head; feature i pairs with i + D/2 (the
    published ``rotate_half``)."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-np.arange(half, dtype=np.float32) / half)
    angle = np.arange(x.shape[1], dtype=np.float32)[:, None] * inv_freq
    cos, sin = jnp.asarray(np.cos(angle)), jnp.asarray(np.sin(angle))
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _short_conv(p, u, sizes: dict, r, depart):
    """``W_out (C * conv(B * x))`` with the causal depthwise convolution
    as shifted slices: ``z_t = sum_j w[j] * (B * x)_{t-(taps-1)+j}``."""
    taps = sizes["conv_L_cache"]
    s = u.shape[1]
    b, c, x = jnp.split(r(u) @ r(p["in_proj"]["kernel"]), 3, -1)
    bx = x if depart == "conv_no_b_gate" else b * x
    padded = jnp.pad(bx, ((0, 0), (taps - 1, 0), (0, 0)))
    kernel = p["conv"]["kernel"]                              # [taps, D]
    first = 1 if depart == "conv_two_taps" else 0
    z = sum(kernel[j] * padded[:, j:j + s] for j in range(first, taps))
    y = z if depart == "conv_no_c_gate" else c * z
    return r(y) @ r(p["out_proj"]["kernel"])


def _attention(p, y, sizes: dict, r, depart):
    """Dense causal softmax attention, one QUERY head at a time
    (``lax.map``; its key-value head is ``head // group``): the [S, S]
    scores of one head at S = 8,192 are 268 MB in float32. An RMSNorm over
    each head's q and k, then the rotation, scale 1/sqrt(head_dim)."""
    group = sizes["num_attention_heads"] // sizes["num_key_value_heads"]
    eps, theta = sizes["norm_eps"], float(sizes["rope_theta"])
    q = jnp.einsum("bsd,dhk->bshk", r(y), r(p["q"]["kernel"]))
    kv = jnp.einsum("bsd,dthk->bsthk", r(y), r(p["kv"]["kernel"]))
    k, v = kv[:, :, 0], kv[:, :, 1]
    if depart != "no_head_qk_norm":
        q = _rms_norm(q, p["q_norm"]["scale"], eps)
        k = _rms_norm(k, p["k_norm"]["scale"], eps)
    s, d = q.shape[1], q.shape[-1]
    causal = np.tril(np.ones((s, s), bool))
    k = jnp.repeat(jnp.moveaxis(k, 2, 0), group, axis=0)      # [h, b, s, d]
    v = jnp.repeat(jnp.moveaxis(v, 2, 0), group, axis=0)

    def one_head(qkv):
        q_h, k_h, v_h = qkv                                   # [b, s, d]
        q_h, k_h = r(_rope(q_h, theta)), r(_rope(k_h, theta))
        scores = jnp.einsum("bqk,bsk->bqs", q_h, k_h) / np.sqrt(d)
        probs = r(jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1))
        return r(jnp.einsum("bqs,bsk->bqk", probs, r(v_h)))

    ctx = jax.lax.map(one_head, (jnp.moveaxis(q, 2, 0), k, v))
    return jnp.einsum("hbqk,hkd->bqd", ctx, r(p["out"]["kernel"]))


def _routed(p, bias, y, sizes: dict, r, depart):
    """The part of ``sum_j g_j E_j(y)`` that the HELD experts give: every
    token through each held expert, times a mask of the router's choice
    (no sort, no grouped matmul, no kernel). The router scores all
    ``num_experts_routed`` experts and keeps ``num_experts_per_tok`` of
    them; what the absent ones would add is left out, as on the chip."""
    routed, held = sizes["num_experts_routed"], sizes["num_experts"]
    first, top_k = sizes["first_expert"], sizes["num_experts_per_tok"]
    logits = y @ p["router"]["kernel"]
    if depart == "softmax_scores":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        scores = jax.nn.sigmoid(logits)
    ranked = scores if depart == "no_expert_bias" else scores + bias
    # The k largest; equal values go to the lower index.
    by_size = jnp.argsort(-ranked, axis=-1, stable=True)
    mask = jnp.argsort(by_size, axis=-1) < top_k
    weights = jnp.where(mask, scores, 0.0)
    if depart != "gates_not_normalised":
        weights = weights / (weights.sum(axis=-1, keepdims=True) + 1e-6)
    weights = weights * sizes["routed_scaling_factor"]
    y, out = r(y), jnp.zeros_like(y)
    # ``uncut_layer``: absent expert e computes with held expert
    # e mod held's weights (it has none of its own here).
    experts = range(routed) if depart == "uncut_layer" else range(
        first, first + held
    )
    for e0 in range(0, len(experts), EXPERTS_AT_ONCE):
        ids = list(experts[e0:e0 + EXPERTS_AT_ONCE])
        local = np.asarray([(e - first) % held for e in ids])
        h = jax.nn.silu(
            jnp.einsum("td,edf->tef", y, r(p["w_gate"][local]))
        ) * jnp.einsum("td,edf->tef", y, r(p["w_up"][local]))
        part = jnp.einsum("tef,efd->ted", r(h), r(p["w_down"][local]))
        out = out + jnp.einsum("ted,te->td", part, weights[:, np.asarray(ids)])
    return out


def _forward(params, ids, sizes: dict, trunk=None, depart=None):
    """Logits of the published stack (``transformers``' Lfm2Moe modelling
    code, written from memory: no network), straightforward float32
    ``jax.numpy`` on the program's parameter tree:

        x = E[ids]
        x = x + operator(rms(x))              (conv | full_attention)
        x = x + ffn(rms(x))                   (dense SwiGLU | routed, held part)
        logits = rms(x) E^T

    ``trunk`` is None for the reference; a dtype rounds the blocks'
    weights and every matmul's inputs to it (router, scores, norms and the
    head stay float32, as the configuration states), which shows what the
    tolerance refuses. ``depart`` names one of ``DEPARTURES``. Departures
    from the published code, all the program's: k and v come from one
    fused projection and the dense FFN's gate and up from one; the
    experts' matrices are stacked ``[held, D, F]``; ``expert_bias`` lives
    in the collection ``buffers`` (the same mathematics)."""
    if depart is not None and depart not in DEPARTURES:
        raise ValueError(f"unknown departure {depart!r}")
    enc = params["params"]["encoder"]
    buffers = params.get("buffers", {}).get("encoder", {})
    eps = sizes["norm_eps"]
    if trunk is None:
        r = lambda a: a  # noqa: E731
    else:
        r = lambda a: a.astype(trunk).astype(jnp.float32)  # noqa: E731
    table = enc["tok_embed"]["embedding"]
    x = r(table)[ids]
    b, s, d = x.shape
    for i, kind in enumerate(sizes["layer_types"]):
        blk = enc[f"block_{i}"]
        if kind == "conv":
            y = _rms_norm(x, blk["ln_conv"]["scale"], eps)
            x = x + _short_conv(blk["conv"], y, sizes, r, depart)
        else:
            y = _rms_norm(x, blk["ln_attn"]["scale"], eps)
            x = x + _attention(blk["attn"], y, sizes, r, depart)
        y = _rms_norm(x, blk["ln_mlp"]["scale"], eps)
        if i < sizes["num_dense_layers"]:
            gate, up = jnp.split(r(y) @ r(blk["mlp_in"]["kernel"]), 2, -1)
            x = x + r(jax.nn.silu(gate) * up) @ r(blk["mlp_out"]["kernel"])
        else:
            bias = buffers[f"block_{i}"]["moe"]["expert_bias"]
            x = x + _routed(
                blk["moe"], bias, y.reshape(b * s, d), sizes, r, depart
            ).reshape(b, s, d)
    x = _rms_norm(x, enc["ln_final"]["scale"], eps)
    return x @ table.T


def reference_logits(params, ids, sizes: dict, trunk=None, depart=None):
    with jax.default_matmul_precision("highest"):
        return _forward(params, ids, sizes, trunk, depart)


def reference_loss_and_grads(params, ids, sizes: dict):
    """Next-token cross-entropy (the configuration has no auxiliary loss)
    and its gradients with respect to ``params`` (the CPU tests compare
    the program's against them)."""
    def loss(p):
        logp = jax.nn.log_softmax(_forward(p, ids, sizes)[:, :-1], axis=-1)
        return -jnp.mean(
            jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)
        )

    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss)(params)


# ------------------------------------------------------ operation counts

def _layers(sizes: dict, kind: str) -> int:
    return sum(1 for k in sizes["layer_types"] if k == kind)


def _routed_layers(sizes: dict) -> int:
    return sizes["num_hidden_layers"] - sizes["num_dense_layers"]


def _matrix_params(sizes: dict) -> dict:
    """Matrix parameters a token touches, by where: a convolution
    operator's two projections, an attention layer's four, a dense FFN,
    a router, ONE expert, the head."""
    d = sizes["hidden_size"]
    head_dim = d // sizes["num_attention_heads"]
    return {
        "conv": 3 * d * d + d * d,
        "attention": 2 * d * d + 2 * d * sizes["num_key_value_heads"] * head_dim,
        "mlp": 3 * d * sizes["intermediate_size"],
        "router": d * sizes["num_experts_routed"],
        "expert": 3 * d * sizes["moe_intermediate_size"],
        "head": d * sizes["vocab_size"],
    }


def n_params(sizes: dict) -> int:
    """Trained parameters held on this chip (``expert_bias`` is a buffer,
    ``num_experts_routed`` floats a routed layer, and is not among them)."""
    m = _matrix_params(sizes)
    d = sizes["hidden_size"]
    head_dim = d // sizes["num_attention_heads"]
    routed, dense = _routed_layers(sizes), sizes["num_dense_layers"]
    return (
        _layers(sizes, "conv") * (m["conv"] + sizes["conv_L_cache"] * d)
        + _layers(sizes, "full_attention") * (m["attention"] + 2 * head_dim)
        + dense * m["mlp"]
        + routed * (m["router"] + sizes["num_experts"] * m["expert"])
        + sizes["num_hidden_layers"] * 2 * d
        + m["head"] + d          # the tied table once, the final norm
    )


def held_pairs_per_step(sizes: dict, traffic: dict, batch: int) -> float:
    """(token, expert) pairs of one step that landed on experts held here,
    over all routed layers: what the program counted on the device over
    its last epoch (gauge ``moe/held_pairs_per_step``), so that no share
    of a peak reads high or low because routing sent this chip more or
    fewer rows than uniform; before the first epoch, the expectation at
    uniform routing, ``T * k * held / routed`` a layer."""
    from raydp_tpu.utils.profiling import metrics

    counted = metrics.gauge_value("moe/held_pairs_per_step")
    if counted:
        return float(counted)
    pairs = batch * traffic["seq_len"] * sizes["num_experts_per_tok"]
    return (_routed_layers(sizes) * pairs * sizes["num_experts"]
            / sizes["num_experts_routed"])


def moe_flops_per_step(sizes: dict, traffic: dict, batch: int) -> float:
    """Operations of the grouped matmuls of one step, forward and
    backward: the pairs on held experts (``held_pairs_per_step``), three
    ``[D, F]`` matrices a row, 2 operations a multiply-add, 3 passes
    (forward, input gradient, weight gradient)."""
    per_row = 2 * _matrix_params(sizes)["expert"]
    return 3.0 * held_pairs_per_step(sizes, traffic, batch) * per_row


def attention_flops_per_step(sizes: dict, traffic: dict, batch: int) -> float:
    """Operations of causal attention's kernels in one step: the pairs
    that exist, ``S(S+1)/2`` a QUERY head, ``2 x 2 x head_dim`` operations
    a pair forward (scores and mixing), and 2.5 times that backward (the
    blockwise backward recomputes the scores: 5 matmuls for 2)."""
    s, d = traffic["seq_len"], sizes["hidden_size"]
    forward = 4.0 * (s * (s + 1) / 2) * d
    return _layers(sizes, "full_attention") * batch * forward * 3.5


def conv_bytes_per_step(sizes: dict, traffic: dict, batch: int) -> float:
    """Bytes the gates and convolutions of one step have to move whatever
    the algorithm: ``B``, ``C``, ``x`` read and the gated output written
    once forward (compute dtype); those and their gradients once
    backward. The kernel's ``taps x D`` floats are nothing beside them."""
    width = jnp.dtype(sizes["compute_dtype"]).itemsize
    forward = width * 4 * sizes["hidden_size"]
    tokens = batch * traffic["seq_len"]
    return 3.0 * _layers(sizes, "conv") * tokens * forward


def flops_per_sample(sizes: dict, traffic: dict) -> float:
    """Operations the forward and backward passes need for one sequence:
    3 x (2 x matrix parameters a token touches x tokens + causal
    attention's scores and mixing over the pairs that exist). A token
    touches its layer's operator, a dense FFN or a router, and the head;
    the experts are counted by the pairs that landed on held ones
    (``held_pairs_per_step``). The tied table counts once, as the head
    (the lookup is a gather); norms, gates and the 3-tap convolution are
    not matmuls; nothing recomputed is counted."""
    s, d = traffic["seq_len"], sizes["hidden_size"]
    m = _matrix_params(sizes)
    attn = _layers(sizes, "full_attention")
    per_token = (_layers(sizes, "conv") * m["conv"] + attn * m["attention"]
                 + sizes["num_dense_layers"] * m["mlp"]
                 + _routed_layers(sizes) * m["router"] + m["head"])
    batch = traffic["per_chip_batch"]
    experts = held_pairs_per_step(sizes, traffic, batch) / batch * m["expert"]
    attention = attn * 4 * d * s * (s + 1) / 2
    return 3.0 * (2 * (per_token * s + experts) + attention)


def bytes_per_step(sizes: dict, traffic: dict, batch: int) -> float:
    """Bytes one chip's step has to move whatever the schedule: every
    parameter, its gradient and both AdamW moments read and written once
    in float32, and the batch read. Activations are left out, so this is
    a lower bound."""
    return 8.0 * 4 * n_params(sizes) + 4.0 * batch * traffic["seq_len"]
