"""Granite-4.0-H style hybrid decoder LM (Mamba-2 layers beside
grouped-query attention in one stack): how the benchmark builds it through
the program, its plain reference (logits, and loss with gradients for the
CPU tests), and its operation counts.

Sizes come from the configuration's JSON (the key names of the model's
``config.json``, ``model_type`` granitemoehybrid without experts). A later
configuration of the same family adds a JSON that names this builder;
nothing here knows a cell.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# At the top, not in the functions: a program without this architecture
# (the parent of the PR that brought it) fails when the cell is loaded,
# before it starts a cluster or takes the chip.
from raydp_tpu.models.transformer import CausalLM, granite_h_micro

# Program logits (bf16 trunk, float32 scan decays and states, float32 tied
# head) against the float32 "highest" reference on ALL 4,096 positions of
# one seeded sequence, as the largest absolute difference over the largest
# reference magnitude (``harness.check_reference``), on the state the
# run's training left. There is no routing here, so nothing moves single
# positions by a rank swap: the error is the trunk's bf16 rounding carried
# through six layers, and the plain reference with its trunk rounded to
# bfloat16 reads half of it (0.12-0.18%).
#
# Measured on the chip at the published widths (PERF.md section 6, PR 30),
# after a 30 s run under the configuration's optimizer, eight seeds:
# 0.171-0.276% (0.30% at init). Departures on such states, three seeds:
# the softmax scale 1/8 for 1/64 2.16-2.47% (it shows at the first
# positions, where attention has few keys to average over), chunks scanned
# independently 8.07-11.09%, a trunk in float8_e4m3 (the precision below
# the stated one) 28.6-31.5%, a missing residual multiplier 64-76%, the
# convolution's bias or D x dropped and the gate after the norm 89-102%.
# 1% is 3.6 times the worst run (under the four times allowed) and under
# half the smallest departure. Under a constant 2e-5 from step 0 (ISSUE
# 30's rate) the same runs read 0.35-0.55% and the softmax-scale departure
# 0.14%: that is why the rate is warmed up (the JSON's ``assumed``).
TOLERANCE = 0.01
CHECK_ROWS = 1

# What the tolerance has to refuse, each a change to the mathematics that
# ``_forward`` can make on request (``depart=``); the tests and PERF.md
# show that each reads above ``TOLERANCE``.
DEPARTURES = (
    "softmax_scale",        # 1/sqrt(head_dim) = 1/8 in place of 1/64
    "residual_multiplier",  # branches added unscaled
    "gate_after_norm",      # rms(y) · w · silu(z), not rms(y · silu(z)) · w
    "no_skip",              # D · x dropped
    "no_conv_bias",         # the convolution's bias dropped
    "independent_chunks",   # no state carried across chunk boundaries
)


def model_config(sizes: dict):
    inner = sizes["mamba_n_heads"] * sizes["mamba_d_head"]
    if (sizes["num_local_experts"] or sizes["num_experts_per_tok"]
            or sizes["shared_intermediate_size"] != sizes["intermediate_size"]):
        raise ValueError("routed experts are not in this builder")
    if inner != sizes["mamba_expand"] * sizes["hidden_size"]:
        raise ValueError("mamba heads x head size != expand x hidden")
    if (sizes["position_embedding_type"] != "nope" or sizes["attention_bias"]
            or sizes["mamba_proj_bias"] or not sizes["mamba_conv_bias"]
            or not sizes["tie_word_embeddings"]
            or sizes["hidden_act"] != "silu"
            or sizes["normalization_function"] != "rmsnorm"):
        raise ValueError("not the block this builder writes down")
    if len(sizes["layer_types"]) != sizes["num_hidden_layers"]:
        raise ValueError("layer_types does not name every layer")
    return granite_h_micro(
        vocab_size=sizes["vocab_size"],
        d_model=sizes["hidden_size"],
        n_heads=sizes["num_attention_heads"],
        n_kv_heads=sizes["num_key_value_heads"],
        n_layers=sizes["num_hidden_layers"],
        layer_types=tuple(sizes["layer_types"]),
        d_ff=sizes["intermediate_size"],
        max_len=sizes["max_position_embeddings"],
        norm_eps=sizes["rms_norm_eps"],
        attention_scale=sizes["attention_multiplier"],
        embedding_multiplier=float(sizes["embedding_multiplier"]),
        residual_multiplier=sizes["residual_multiplier"],
        logits_scaling=float(sizes["logits_scaling"]),
        ssm_heads=sizes["mamba_n_heads"],
        ssm_head_dim=sizes["mamba_d_head"],
        ssm_state=sizes["mamba_d_state"],
        ssm_groups=sizes["mamba_n_groups"],
        ssm_conv=sizes["mamba_d_conv"],
        ssm_chunk=sizes["mamba_chunk_size"],
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
    """Arguments of ``JAXEstimator`` for this configuration."""
    return dict(
        model=CausalLM(model_config(sizes)),
        optimizer=_optimizer(sizes["optimizer"]),
        loss="lm_ce",
        self_supervised=True,
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


def _mamba(p, y, sizes: dict, r, depart):
    """The Mamba-2 mixer with its scan as the RECURRENCE ITSELF, one
    ``lax.scan`` step a token and no chunks:
    ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t ⊗ B_t``, ``y_t = h_t C_t + D x_t``."""
    heads, hd, n, g = (sizes["mamba_n_heads"], sizes["mamba_d_head"],
                       sizes["mamba_d_state"], sizes["mamba_n_groups"])
    inner, taps, eps = heads * hd, sizes["mamba_d_conv"], sizes["rms_norm_eps"]
    b, s, _ = y.shape
    z, xbc, dt = jnp.split(
        r(y) @ r(p["in_proj"]["kernel"]), [inner, 2 * inner + 2 * g * n], -1
    )
    # The causal depthwise convolution as ``taps`` shifted slices.
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = sum(
        r(p["conv"]["kernel"])[j] * padded[:, j:j + s] for j in range(taps)
    )
    if depart != "no_conv_bias":
        conv = conv + r(p["conv"]["bias"])
    xbc = r(jax.nn.silu(conv))
    x, B, C = jnp.split(xbc, [inner, inner + g * n], -1)
    x = x.reshape(b, s, heads, hd)
    B = jnp.repeat(B.reshape(b, s, g, n), heads // g, axis=2)
    C = jnp.repeat(C.reshape(b, s, g, n), heads // g, axis=2)
    ssd = p["ssd"]
    dt = jax.nn.softplus(dt + ssd["dt_bias"])            # [b, s, heads]
    decay = jnp.exp(dt * -jnp.exp(ssd["A_log"]))
    if depart == "independent_chunks":
        # What a chunked scan computes when nothing is carried across a
        # chunk boundary: the state is dropped at each chunk's first token.
        first = np.arange(s) % sizes["mamba_chunk_size"] == 0
        decay = jnp.where(first[None, :, None], 0.0, decay)

    def token(state, t):
        decay_t, dtx_t, b_t, c_t = t
        state = decay_t[..., None, None] * state + (
            dtx_t[..., None] * b_t[..., None, :]
        )
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t)

    _, out = jax.lax.scan(
        token, jnp.zeros((b, heads, hd, n), jnp.float32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (
            decay, dt[..., None] * x, B, C
        )),
    )
    out = jnp.moveaxis(out, 0, 1)
    if depart != "no_skip":
        out = out + ssd["D"][:, None] * x
    out, gate = out.reshape(b, s, inner), jax.nn.silu(z)
    scale = p["gate_norm"]["scale"]
    if depart == "gate_after_norm":
        out = _rms_norm(out, scale, eps) * gate
    else:
        out = _rms_norm(out * gate, scale, eps)
    return r(out) @ r(p["out_proj"]["kernel"])


def _attention(p, y, sizes: dict, r, depart):
    """Dense causal softmax attention, K and V repeated for the query
    heads of their group, no positions, the published softmax scale. One
    key-value group at a time (``lax.map``): the [S, S] scores of 32 heads
    at once are 2.1 GB beside the training state."""
    group = sizes["num_attention_heads"] // sizes["num_key_value_heads"]
    scale = sizes["attention_multiplier"]
    if depart == "softmax_scale":
        scale = p["q"]["kernel"].shape[-1] ** -0.5
    q = jnp.einsum("bsd,dhk->bshk", r(y), r(p["q"]["kernel"]))
    kv = jnp.einsum("bsd,dthk->bsthk", r(y), r(p["kv"]["kernel"]))
    b, s, h, d = q.shape
    causal = np.tril(np.ones((s, s), bool))

    def one_group(qkv):
        q_g, k_g, v_g = qkv               # [b, s, group, d], [b, s, d] x 2
        k_g = jnp.repeat(k_g[:, :, None], group, axis=2)
        v_g = jnp.repeat(v_g[:, :, None], group, axis=2)
        scores = jnp.einsum("bqhk,bshk->bhqs", q_g, k_g) * scale
        probs = r(jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1))
        return r(jnp.einsum("bhqs,bshk->bqhk", probs, v_g))

    ctx = jax.lax.map(one_group, (
        jnp.moveaxis(r(q).reshape(b, s, h // group, group, d), 2, 0),
        jnp.moveaxis(r(kv[:, :, 0]), 2, 0), jnp.moveaxis(r(kv[:, :, 1]), 2, 0),
    ))
    ctx = jnp.moveaxis(ctx, 0, 2).reshape(b, s, h, d)
    return jnp.einsum("bqhk,hkd->bqd", ctx, r(p["out"]["kernel"]))


def _forward(params, ids, sizes: dict, trunk=None, depart=None):
    """Logits of the published stack (``transformers``' GraniteMoeHybrid /
    Bamba modelling code, written from memory: no network), straightforward
    float32 ``jax.numpy`` on the program's parameter tree:

        x = embedding_multiplier · E[ids]
        x = x + residual_multiplier · mixer(rms(x))       (mamba | attention)
        x = x + residual_multiplier · W_out(silu(g) · u),  [g, u] = W_in rms(x)
        logits = rms(x) Eᵀ / logits_scaling

    ``trunk`` is None for the reference; a dtype rounds the blocks' weights
    and every matmul's inputs to it (the scan's decays and states, the
    norms and the head stay float32, as the configuration states), which
    shows what the tolerance refuses. ``depart`` names one of
    ``DEPARTURES``. Departures from the published code, all the program's:
    k and v come from one fused projection and the MLP's gate and up from
    one (the same mathematics); the scan's parameters live under ``ssd``,
    the convolution's under ``conv``."""
    if depart is not None and depart not in DEPARTURES:
        raise ValueError(f"unknown departure {depart!r}")
    enc = params["params"]["encoder"]
    eps = sizes["rms_norm_eps"]
    mult = 1.0 if depart == "residual_multiplier" else (
        sizes["residual_multiplier"]
    )
    if trunk is None:
        r = lambda a: a  # noqa: E731
    else:
        r = lambda a: a.astype(trunk).astype(jnp.float32)  # noqa: E731
    table = enc["tok_embed"]["embedding"]
    x = sizes["embedding_multiplier"] * r(table)[ids]
    for i, kind in enumerate(sizes["layer_types"]):
        blk = enc[f"block_{i}"]
        if kind == "mamba":
            y = _rms_norm(x, blk["ln_mamba"]["scale"], eps)
            x = x + mult * _mamba(blk["mamba"], y, sizes, r, depart)
        else:
            y = _rms_norm(x, blk["ln_attn"]["scale"], eps)
            x = x + mult * _attention(blk["attn"], y, sizes, r, depart)
        y = r(_rms_norm(x, blk["ln_mlp"]["scale"], eps))
        gate, up = jnp.split(y @ r(blk["mlp_in"]["kernel"]), 2, -1)
        x = x + mult * (
            r(jax.nn.silu(gate) * up) @ r(blk["mlp_out"]["kernel"])
        )
    x = _rms_norm(x, enc["ln_final"]["scale"], eps)
    return x @ table.T / sizes["logits_scaling"]


def reference_logits(params, ids, sizes: dict, trunk=None, depart=None):
    with jax.default_matmul_precision("highest"):
        return _forward(params, ids, sizes, trunk, depart)


def reference_loss_and_grads(params, ids, sizes: dict):
    """Next-token cross-entropy and its gradients with respect to
    ``params`` (the CPU tests compare the program's against them)."""
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


def _matrix_params(sizes: dict) -> dict:
    """Matrix parameters a token touches, by where: a mamba layer's
    projections, an attention layer's, either layer's MLP, the head."""
    d, f = sizes["hidden_size"], sizes["intermediate_size"]
    inner = sizes["mamba_n_heads"] * sizes["mamba_d_head"]
    bc = 2 * sizes["mamba_n_groups"] * sizes["mamba_d_state"]
    head_dim = d // sizes["num_attention_heads"]
    return {
        "mamba": d * (2 * inner + bc + sizes["mamba_n_heads"]) + inner * d,
        "attention": 2 * d * d + 2 * d * sizes["num_key_value_heads"] * head_dim,
        "mlp": 3 * d * f,
        "head": d * sizes["vocab_size"],
    }


def n_params(sizes: dict) -> int:
    m = _matrix_params(sizes)
    d = sizes["hidden_size"]
    inner = sizes["mamba_n_heads"] * sizes["mamba_d_head"]
    channels = inner + 2 * sizes["mamba_n_groups"] * sizes["mamba_d_state"]
    mamba = (m["mamba"] + (sizes["mamba_d_conv"] + 1) * channels
             + 3 * sizes["mamba_n_heads"] + inner)
    return (
        _layers(sizes, "mamba") * (mamba + m["mlp"] + 2 * d)
        + _layers(sizes, "attention") * (m["attention"] + m["mlp"] + 2 * d)
        + m["head"] + d          # the tied table once, the final norm
    )


def ssd_flops_per_token(sizes: dict) -> float:
    """Forward operations of one token in one layer's scan that no
    algorithm can avoid: ``C Bᵀ`` and ``(C Bᵀ ∘ L) X`` over the causal pairs
    inside a chunk, ``(Q + 1) / 2`` a token; the chunk's state ``B ⊗ x``;
    the carried-in part ``C · state``. 2 operations a multiply-add."""
    n = sizes["mamba_n_groups"] * sizes["mamba_d_state"]
    hp = sizes["mamba_n_heads"] * sizes["mamba_d_head"]
    pairs = (sizes["mamba_chunk_size"] + 1) / 2
    state = sizes["mamba_d_state"] * hp
    return 2 * n * pairs + 2 * hp * pairs + 2 * 2 * state


def ssd_flops_per_step(sizes: dict, traffic: dict, batch: int) -> float:
    """The scans of one step, forward and backward (twice the forward)."""
    tokens = batch * traffic["seq_len"]
    return 3.0 * _layers(sizes, "mamba") * tokens * ssd_flops_per_token(sizes)


def ssd_bytes_per_step(sizes: dict, traffic: dict, batch: int) -> float:
    """Bytes the scans of one step have to move whatever the algorithm:
    ``x``, ``B``, ``C`` (compute dtype) and ``dt`` (float32) read and ``y``
    written once forward; those and their gradients once backward."""
    width = jnp.dtype(sizes["compute_dtype"]).itemsize
    hp = sizes["mamba_n_heads"] * sizes["mamba_d_head"]
    bc = 2 * sizes["mamba_n_groups"] * sizes["mamba_d_state"]
    forward = width * (2 * hp + bc) + 4 * sizes["mamba_n_heads"]
    tokens = batch * traffic["seq_len"]
    return 3.0 * _layers(sizes, "mamba") * tokens * forward


def attention_flops_per_step(sizes: dict, traffic: dict, batch: int) -> float:
    """Operations of causal attention's kernels in one step: the pairs
    that exist, ``S(S+1)/2`` a QUERY head (32 of 64), ``2 × 2 × head_dim``
    operations a pair forward (scores and mixing), and 2.5 times that
    backward (the blockwise backward recomputes the scores: 5 matmuls for
    2)."""
    s, d = traffic["seq_len"], sizes["hidden_size"]
    forward = 4.0 * (s * (s + 1) / 2) * d
    return _layers(sizes, "attention") * batch * forward * 3.5


def flops_per_sample(sizes: dict, traffic: dict) -> float:
    """Operations the forward and backward passes need for one sequence:
    3 x (2 x matrix parameters a token touches x tokens + causal
    attention's scores and mixing over the pairs that exist + the scans'
    unavoidable count). The tied table counts once, as the head (the
    lookup is a gather); norms, the convolution and the gate are not
    matmuls; nothing recomputed is counted."""
    s, d = traffic["seq_len"], sizes["hidden_size"]
    m = _matrix_params(sizes)
    mamba, attn = _layers(sizes, "mamba"), _layers(sizes, "attention")
    per_token = (mamba * (m["mamba"] + m["mlp"])
                 + attn * (m["attention"] + m["mlp"]) + m["head"])
    attention = attn * 4 * d * s * (s + 1) / 2
    scan = mamba * s * ssd_flops_per_token(sizes)
    return 3.0 * (2 * per_token * s + attention + scan)


def bytes_per_step(sizes: dict, traffic: dict, batch: int) -> float:
    """Bytes one chip's step has to move whatever the schedule: every
    parameter, its gradient and both AdamW moments read and written once
    in float32, and the batch read. Activations are left out, so this is
    a lower bound."""
    return 8.0 * 4 * n_params(sizes) + 4.0 * batch * traffic["seq_len"]
