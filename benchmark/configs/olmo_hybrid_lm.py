"""Olmo-Hybrid style decoder LM (Gated DeltaNet, a gated delta rule with
ONE scalar decay a head, keys of one width and values of another, in
three layers of four; full attention with a norm over the whole q and k
projections and no positions in the fourth; a dense SwiGLU FFN in every
layer; an RMSNorm on each sublayer's OUTPUT and none on its input) as ONE
PIPELINE STAGE of the model: how the benchmark builds it through the
program, its plain reference (logits, and loss with gradients for the CPU
tests), and its operation and byte counts.

Sizes come from the configuration's JSON (the key names of the model's
``config.json``, ``model_type`` olmo_hybrid; the group ``gdn`` holds the
scan's chunk). A later configuration of the same family adds a JSON that
names this builder; nothing here knows a cell.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# At the top, not in the functions: a program without this architecture
# (the parent of the PR that brought it) fails when the cell is loaded,
# before it starts a cluster or takes the chip.
from raydp_tpu.models.gdn import GDNConfig
from raydp_tpu.models.transformer import CausalLM, olmo_hybrid_7b

# Program logits (bf16 trunk; float32 decays, their cumulative sums, beta,
# the chunks' decay matrices and triangular inverses, the chunk states,
# norms inside, softmax and head) against the float32 "highest" reference,
# its delta rule advanced token by token, on ALL 4,096 positions of one
# seeded sequence of the timed shape, as the largest absolute difference
# over the largest reference magnitude (``harness.check_reference``), on
# the state the run's training left.
#
# What sets the error is the bf16 trunk: the plain reference with its
# trunk rounded to bfloat16 reads 1.21% from the program on a state where
# the program is 1.45% from float32. Measured on the chip at the published
# widths (PERF.md section 6, PR 63) after a 30 s run, twelve runs over
# twelve seeds: 1.37-2.14% (1.37, 1.41, 1.44, 1.45, 1.46, 1.64, 1.68,
# 1.72, 1.91, 1.96, 2.04, 2.14). On such a state (one seed) the precision below the stated one, a
# trunk in float8_e4m3, reads 15.8%, and the departures: beta not doubled
# 23.3%, no decay 97.9%, the norm moved to the sublayer's input 194%. 5%
# is 2.3 times the program's largest reading, 3.2 times under the float8
# trunk's and 4.7 times under the nearest seen departure.
#
# One departure the check CANNOT tell from the program's own rounding,
# pinned by the float32 CPU tests: ``state_bfloat16`` reads 1.46% against
# the program's 1.45% on the same state. A state's three products a token
# in bfloat16 move the logits by less than the trunk's bf16 rounding does,
# so no tolerance that bf16 leaves room for can see it (ISSUE 63 asked
# that it be SEEN; the CPU tests, float32: several thousand times the
# program's own error, there and in ``tests/test_gdn.py`` on the scan
# alone).
TOLERANCE = 0.05
UNSEEN_ON_THE_CHIP = ("state_bfloat16",)
CHECK_ROWS = 1
# The reference runs in blocks so that a sequence fits beside 10.4 GiB of
# resident training state: attention a head and this many query rows at a
# time, the head this many vocabulary columns. The delta rule's state is
# 2.2 MB for all 30 heads: one scan over the tokens carries them together.
QUERY_ROWS_AT_ONCE = 512
VOCAB_AT_ONCE = 4096
L2_EPS = 1e-6
LINEAR, FULL = "linear_attention", "full_attention"

# Changes to the mathematics that ``_forward`` can make on request
# (``depart=``). The tests show that each but ``UNSEEN_ON_THE_CHIP`` reads
# above ``TOLERANCE`` at the tiny size in float32, PERF.md what each reads
# at the published widths.
DEPARTURES = (
    "beta_not_doubled",    # beta = sigmoid(.), in (0, 1)
    "no_decay",            # g = 0: the plain delta rule
    "norm_on_input",       # x + F(rms(x)): the pre-norm block of the others
    "state_bfloat16",      # the state's three products a token in bfloat16
)


def model_config(sizes: dict):
    kinds = sizes["layer_types"]
    if (sizes["model_type"] != "olmo_hybrid" or sizes["hidden_act"] != "silu"
            or sizes["tie_word_embeddings"] or sizes["attention_bias"]
            or sizes["rope_parameters"]["rope_theta"] is not None
            or len(kinds) != sizes["num_hidden_layers"]
            or set(kinds) - {LINEAR, FULL}
            or sizes["num_key_value_heads"] != sizes["num_attention_heads"]
            or sizes["hidden_size"] % sizes["num_attention_heads"]
            or sizes["linear_num_key_heads"] != sizes["linear_num_value_heads"]):
        raise ValueError("not the block this builder writes down")
    return olmo_hybrid_7b(
        vocab_size=sizes["vocab_size"],
        d_model=sizes["hidden_size"],
        n_heads=sizes["num_attention_heads"],
        n_layers=sizes["num_hidden_layers"],
        layer_types=tuple(
            ("gdn" if kind == LINEAR else "attention") + ":swiglu"
            for kind in kinds
        ),
        d_ff=sizes["intermediate_size"],
        max_len=sizes["max_position_embeddings"],
        norm_eps=sizes["rms_norm_eps"],
        gdn=GDNConfig(
            heads=sizes["linear_num_value_heads"],
            key_dim=sizes["linear_key_head_dim"],
            value_dim=sizes["linear_value_head_dim"],
            conv_taps=sizes["linear_conv_kernel_dim"],
            chunk=sizes["gdn"]["chunk"],
            neg_eigval=sizes["linear_allow_neg_eigval"],
        ),
        embed_init_std=sizes["init"]["embedding_std"],
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


def _conv(x, kernel):
    """``out_t = sum_j kernel[j] x_{t-(taps-1)+j}`` over ``x`` [S, C], zeros
    before the first token; ``kernel`` [taps, C], no bias."""
    taps, s = kernel.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1]), x.dtype), x])
    return sum(kernel[j] * padded[j:j + s] for j in range(taps))


def _delta_rule(q, k, v, g, beta, depart):
    """The recurrence TOKEN BY TOKEN, all heads: ``q``, ``k`` [S, h, d_k],
    ``v`` [S, h, d_v], ``g``, ``beta`` [S, h]; the state [h, d_k, d_v]
    starts at zero. Returns ``o`` [S, h, d_v]."""
    if depart == "state_bfloat16":
        low = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    else:
        low = lambda a: a  # noqa: E731

    def step(state, token):
        q_t, k_t, v_t, g_t, beta_t = token
        decayed = jnp.exp(g_t)[:, None, None] * state
        read = jnp.einsum("hkv,hk->hv", low(decayed), low(k_t))
        state = decayed + jnp.einsum(
            "hk,hv->hkv", low(k_t), low(beta_t[:, None] * (v_t - read))
        )
        return state, jnp.einsum("hkv,hk->hv", low(state), low(q_t))

    zero = jnp.zeros((k.shape[1], k.shape[2], v.shape[2]), jnp.float32)
    _, out = jax.lax.scan(step, zero, (q, k, v, g, beta))
    return out


def _gdn(p, x, sizes: dict, r, depart):
    """One sequence ``x`` [S, D] through a Gated DeltaNet mixer."""
    heads, eps = sizes["linear_num_value_heads"], sizes["rms_norm_eps"]
    d_k, d_v = sizes["linear_key_head_dim"], sizes["linear_value_head_dim"]
    s = x.shape[0]

    def branch(name, width):
        y = _conv(r(x) @ r(p[f"{name}_proj"]["kernel"]),
                  p["conv"][name]["kernel"])
        return jax.nn.silu(y).reshape(s, heads, width)

    def unit(y):
        return y / jnp.sqrt(jnp.sum(y * y, axis=-1, keepdims=True) + L2_EPS)

    q, k = unit(branch("q", d_k)) * d_k ** -0.5, unit(branch("k", d_k))
    v = branch("v", d_v)
    decay = p["decay"]
    g = -jnp.exp(decay["A_log"]) * jax.nn.softplus(
        r(r(x) @ r(decay["proj"]["kernel"])) + decay["dt_bias"]
    )
    if depart == "no_decay":
        g = jnp.zeros_like(g)
    beta = jax.nn.sigmoid(r(r(x) @ r(p["beta"]["kernel"])))
    if sizes["linear_allow_neg_eigval"] and depart != "beta_not_doubled":
        beta = 2.0 * beta
    o = _delta_rule(r(q), r(k), r(v), g, beta, depart)
    z = r(r(x) @ r(p["g_proj"]["kernel"])).reshape(o.shape)
    o = _rms_norm(o, p["gate_norm"]["scale"], eps) * jax.nn.silu(z)
    return r(o.reshape(s, heads * d_v)) @ r(p["out"]["kernel"])


def _attention(p, x, sizes: dict, r):
    """One sequence ``x`` [S, D]. Dense causal softmax attention, a head
    and ``QUERY_ROWS_AT_ONCE`` query rows at a time (``lax.map``: a
    block's scores over 4,096 keys are 8 MB in float32). ``q`` and ``k``
    are normed over the WHOLE projection before the split into heads;
    nothing is rotated."""
    eps, s = sizes["rms_norm_eps"], x.shape[0]
    heads = sizes["num_attention_heads"]
    width = sizes["hidden_size"] // heads
    qkv = jnp.einsum("sd,dthk->tshk", r(x), r(p["qkv"]["kernel"]))
    q = _rms_norm(qkv[0].reshape(s, -1), p["q_norm"]["scale"], eps)
    k = _rms_norm(qkv[1].reshape(s, -1), p["k_norm"]["scale"], eps)
    q, k = (jnp.moveaxis(a.reshape(s, heads, width), 1, 0) for a in (q, k))
    v = jnp.moveaxis(qkv[2], 1, 0)                            # [H, S, 128]
    rows = min(QUERY_ROWS_AT_ONCE, s)
    if s % rows:
        raise ValueError(f"{s} positions in blocks of {rows}")
    key_at = np.arange(s)

    def one_head(qkv_h):
        q_h, k_h, v_h = qkv_h

        def one_block(args):
            q_b, r0 = args
            see = key_at[None, :] <= r0 + np.arange(rows)[:, None]
            scores = (r(q_b) @ r(k_h).T) * width ** -0.5
            probs = r(jax.nn.softmax(jnp.where(see, scores, -jnp.inf), -1))
            return probs @ r(v_h)

        blocks = jax.lax.map(
            one_block, (q_h.reshape(s // rows, rows, -1),
                        jnp.arange(0, s, rows)),
        )
        return blocks.reshape(s, -1)

    ctx = jax.lax.map(one_head, (q, k, v))                    # [H, S, 128]
    return jnp.einsum("hsk,hkd->sd", r(ctx), r(p["out"]["kernel"]))


def _swiglu(y, w_in, w_out, r):
    gate, up = jnp.split(r(y) @ r(w_in), 2, -1)
    return r(jax.nn.silu(gate) * up) @ r(w_out)


def _forward(params, ids, sizes: dict, trunk=None, depart=None):
    """Logits of the stack as ISSUE 63 writes it down (Gated DeltaNet as
    arXiv:2412.06464 and its public layer parameterise it, the OLMo 2
    family's norms; written from the config and the papers: no network),
    straightforward float32 ``jax.numpy`` on the program's parameter tree,
    one sequence at a time:

        x = E[ids]
        per layer:  h = x + rms_a(GDN(x)  or  attention(x))
                    x = h + rms_f(SwiGLU(h))          (no norm on an input)
        logits = rms(x) W_head

    ``trunk`` is None for the reference; a dtype rounds the blocks'
    weights and every matmul's inputs to it (decays, beta, the state,
    norms and the head stay float32, as the configuration states), which
    shows what the tolerance refuses. ``depart`` names one of
    ``DEPARTURES``."""
    if depart is not None and depart not in DEPARTURES:
        raise ValueError(f"unknown departure {depart!r}")
    enc = params["params"]["encoder"]
    eps = sizes["rms_norm_eps"]
    if trunk is None:
        r = lambda a: a  # noqa: E731
    else:
        r = lambda a: a.astype(trunk).astype(jnp.float32)  # noqa: E731
    head = params["params"]["lm_head"]["kernel"]              # [D, V]

    def sublayer(fn, x, scale):
        if depart == "norm_on_input":
            return x + fn(_rms_norm(x, scale, eps))
        return x + _rms_norm(fn(x), scale, eps)

    def one_sequence(row):
        x = r(enc["tok_embed"]["embedding"])[row]             # [S, D]
        for i, kind in enumerate(sizes["layer_types"]):
            blk = enc[f"block_{i}"]
            if kind == LINEAR:
                x = sublayer(
                    lambda y: _gdn(blk["gdn"], y, sizes, r, depart), x,
                    blk["ln_gdn_out"]["scale"])
            else:
                x = sublayer(
                    lambda y: _attention(blk["attn"], y, sizes, r), x,
                    blk["ln_attn_out"]["scale"])
            x = sublayer(
                lambda y: _swiglu(
                    y, blk["mlp_in"]["kernel"], blk["mlp_out"]["kernel"], r),
                x, blk["ln_mlp_out"]["scale"])
        x = _rms_norm(x, enc["ln_final"]["scale"], eps)
        return jnp.concatenate([
            x @ head[:, v0:v0 + VOCAB_AT_ONCE]
            for v0 in range(0, head.shape[1], VOCAB_AT_ONCE)
        ], axis=-1)

    return jnp.stack([one_sequence(row) for row in ids])


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


# ------------------------------------------------ operation and byte counts

def _layers_of(sizes: dict, kind: str) -> int:
    return sum(1 for k in sizes["layer_types"] if k == kind)


def _gdn_widths(sizes: dict):
    """(heads, all heads' keys, all heads' values) of a linear layer."""
    heads = sizes["linear_num_value_heads"]
    return (heads, heads * sizes["linear_key_head_dim"],
            heads * sizes["linear_value_head_dim"])


def _matrix_params(sizes: dict) -> dict:
    """Matrix parameters a token touches, by where: a Gated DeltaNet mixer
    (q, k, v, gate, output, and the two projections to a value a head),
    the full layer's four projections, the SwiGLU FFN, the head."""
    d = sizes["hidden_size"]
    heads, keys, values = _gdn_widths(sizes)
    return {
        "gdn": d * (2 * keys + 3 * values + 2 * heads),
        "attention": 4 * d * d,
        "mlp": 3 * d * sizes["intermediate_size"],
        "head": d * sizes["vocab_size"],
    }


def n_params(sizes: dict) -> int:
    """Trained parameters held on this chip."""
    m, d = _matrix_params(sizes), sizes["hidden_size"]
    heads, keys, values = _gdn_widths(sizes)
    # Three convolutions, A_log, dt_bias, the head norm's one weight.
    gdn_vectors = (sizes["linear_conv_kernel_dim"] * (2 * keys + values)
                   + 2 * heads + sizes["linear_value_head_dim"])
    return (
        _layers_of(sizes, LINEAR) * (m["gdn"] + gdn_vectors)
        + _layers_of(sizes, FULL) * (m["attention"] + 2 * d)   # q, k norms
        + sizes["num_hidden_layers"] * (m["mlp"] + 2 * d)   # two output norms
        + 2 * m["head"] + d      # embedding and untied head, the final norm
    )


def attention_flops_per_step(sizes: dict, traffic: dict, batch: int) -> float:
    """Operations of the full layers' attention kernels in one step: the
    pairs that exist, ``S(S+1)/2`` a head, 2 operations a multiply-add,
    two products of the head's width forward (scores, mixing) and five
    backward (the score again, ``dq``, ``dk``, ``dp``, ``dv``). Nothing
    recomputed is counted."""
    s = traffic["seq_len"]
    heads = sizes["num_attention_heads"]
    width = sizes["hidden_size"] // heads
    pairs = heads * s * (s + 1) / 2
    return _layers_of(sizes, FULL) * batch * pairs * 2.0 * 7 * width


def gdn_flops_per_token(sizes: dict) -> float:
    """Operations the recurrence costs a token, all heads of one layer,
    forward, whatever computes it: three products of ``2 d_k d_v`` (the
    state read at ``k``, the rank-one update, the state read at ``q``)
    and the decay's ``d_k d_v`` multiplies."""
    return (sizes["linear_num_value_heads"] * 7.0
            * sizes["linear_key_head_dim"] * sizes["linear_value_head_dim"])


def gdn_flops_per_step(sizes: dict, traffic: dict, batch: int) -> float:
    """The delta rule of one step, forward and backward (twice the
    forward)."""
    tokens = batch * traffic["seq_len"]
    return (3.0 * _layers_of(sizes, LINEAR) * tokens
            * gdn_flops_per_token(sizes))


def gdn_bytes_per_step(sizes: dict, traffic: dict, batch: int) -> float:
    """Bytes the delta rule of one step has to move whatever the
    algorithm: ``q``, ``k``, ``v`` (compute dtype), ``g`` and ``beta``
    (float32) read and ``o`` written once forward; those and their
    gradients once backward, each in the dtype the program moves it in."""
    width = jnp.dtype(sizes["compute_dtype"]).itemsize
    heads, keys, values = _gdn_widths(sizes)
    once = width * (2 * keys + 2 * values) + 2 * 4 * heads
    tokens = batch * traffic["seq_len"]
    return 2.0 * _layers_of(sizes, LINEAR) * tokens * once


def flops_per_sample(sizes: dict, traffic: dict) -> float:
    """Operations the forward and backward passes need for one sequence:
    3 x (2 x matrix parameters a token touches x tokens + the full
    layers' scores and mixing over the pairs that exist + the delta
    rule's unavoidable count). The embedding lookup is a gather; norms,
    convolutions and gates are not matmuls; nothing recomputed is counted
    (not the checkpointed forward, not the chunked form's extra
    products)."""
    s, d = traffic["seq_len"], sizes["hidden_size"]
    m = _matrix_params(sizes)
    linear, full = _layers_of(sizes, LINEAR), _layers_of(sizes, FULL)
    per_token = (linear * m["gdn"] + full * m["attention"]
                 + sizes["num_hidden_layers"] * m["mlp"] + m["head"])
    attention = full * 4 * d * s * (s + 1) / 2
    scan = linear * s * gdn_flops_per_token(sizes)
    return 3.0 * (2 * per_token * s + attention + scan)


def bytes_per_step(sizes: dict, traffic: dict, batch: int) -> float:
    """Bytes one chip's step has to move whatever the schedule: every
    parameter, its gradient and both AdamW moments read and written once
    in float32, and the batch read. Activations are left out, so this is
    a lower bound."""
    return 8.0 * 4 * n_params(sizes) + 4.0 * batch * traffic["seq_len"]
