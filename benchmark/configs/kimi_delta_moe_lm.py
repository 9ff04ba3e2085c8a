"""Kimi Linear style decoder LM (Kimi Delta Attention, a gated delta rule
with a decay per channel, in three layers of four; latent attention
without a query latent and without positions in the fourth; a dense FFN
in the leading layer and many routed experts beside a shared one in the
others) as ONE CHIP'S SHARE of an expert-parallel deployment: how the
benchmark builds it through the program, its plain reference given the
same share (logits, and loss with gradients for the CPU tests), and its
operation and byte counts.

Sizes come from the configuration's JSON (the key names of the model's
``config.json``, ``model_type`` kimi_linear). ``num_experts`` is how many
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
from raydp_tpu.models.kda import KDAConfig
from raydp_tpu.models.latent import LatentConfig
from raydp_tpu.models.transformer import CausalLM, kimi_linear_48b_a3b

# Program logits (bf16 trunk; float32 decays, their cumulative sums, beta,
# chunk states, triangular inverse, router, scores, gates, norms and head)
# against the float32 "highest" reference GIVEN THE SAME SHARE, its delta
# rule advanced token by token, on ALL 16,384 positions of one seeded
# sequence, as the largest absolute difference over the largest reference
# magnitude (``harness.check_reference``), on the state the run's training
# left.
#
# What sets the error is the bf16 trunk: the plain reference with its
# trunk rounded to bfloat16 reads what the program reads (1.22% against
# 1.18% on the same state). Measured on the chip at the published widths
# (PERF.md section 6, PR 44) after a 30 s run under the configuration's
# optimizer, nine runs over nine seeds: 1.03-1.21%. Departures on such a
# state (one seed): a trunk in float8_e4m3 (the precision below the stated
# one) 9.7%, the 64 "rope" features rotated 3.2%, no delta term 49%,
# beta = 1 52%, no output gate 64%, one decay a head 71%, the state dropped
# every 64 tokens 78%, no convolution 102%; without the L2 norm of q and k
# the reference's own recurrence diverges (not finite: the comparison
# fails). 2.0% is 1.65 times the worst run and 1.6 times under the
# smallest reading of any seen departure (4.9 times under the float8
# trunk's).
#
# Two departures the check CANNOT tell from the program's own rounding,
# pinned by the float32 CPU tests: ``gates_times_one`` reads 1.21% (a chip
# holds 8 of 256 experts, so the routed part of a token's FFN output is a
# thirty-second of its pairs beside the whole shared expert: PR 36's
# finding), and ``decay_clamped`` 1.178% against the program's 1.178%: a
# clamp of a token's log-decay at -5 changes a decay factor by at most
# e^-5 = 0.7%, and at this init fewer than one channel-token in ten
# thousand passes -5 at all. ISSUE 44 asked that the clamp be SEEN; no
# tolerance that bf16 leaves room for can see it (the CPU tests, float32,
# strong decays: a hundred times the program's own error).
TOLERANCE = 0.02
UNSEEN_ON_THE_CHIP = ("decay_clamped", "gates_times_one")
CHECK_ROWS = 1
# The reference runs in blocks so that 16,384 positions fit beside 9.6 GB
# of training state: latent attention a head and this many query rows at a
# time, the experts this many at a time ([T, 2, F] float32), the head this
# many vocabulary rows. The delta rule's state is 2 MB for all 32 heads:
# one scan over the tokens carries them together.
QUERY_ROWS_AT_ONCE = 512
EXPERTS_AT_ONCE = 2
VOCAB_AT_ONCE = 4096
L2_EPS = 1e-6

# Changes to the mathematics that ``_forward`` can make on request
# (``depart=``). The tests show that each reads above ``TOLERANCE`` at the
# tiny size in float32, PERF.md what each reads at the published widths.
DEPARTURES = (
    "scalar_decay",        # a head's mean of g for all its channels
    "no_delta",            # S_t = Diag(a) S + beta k v^T: no -beta k k^T
    "decay_clamped",       # g no lower than -5 a token
    "independent_chunks",  # the state dropped at every 64th token
    "beta_one",            # beta = 1
    "no_qk_l2norm",        # q and k as the SiLU leaves them (q still scaled)
    "no_short_conv",       # SiLU of the projections, no convolution
    "no_output_gate",      # the per-head norm's output ungated
    "rotary_on_latent",    # the 64 "rope" features rotated at rope_theta
    "gates_times_one",     # routed scaling 1, not 2.446
)
CLAMP = -5.0
CHUNK_OF_DEPARTURE = 64


def _kinds(sizes: dict):
    """True for a KDA layer, False for a latent one, layer by layer, from
    ``linear_attn_config``'s 1-indexed lists."""
    lin, layers = sizes["linear_attn_config"], sizes["num_hidden_layers"]
    kda, full = set(lin["kda_layers"]), set(lin["full_attn_layers"])
    if kda & full or kda | full != set(range(1, layers + 1)):
        raise ValueError("the two lists do not name every layer once")
    return [i + 1 in kda for i in range(layers)]


def model_config(sizes: dict):
    lin = sizes["linear_attn_config"]
    if (sizes["model_type"] != "kimi_linear" or not sizes["mla_use_nope"]
            or sizes["q_lora_rank"] is not None or sizes["rope_scaling"]
            or sizes["moe_router_activation_func"] != "sigmoid"
            or not sizes["moe_renormalize"] or sizes["moe_layer_freq"] != 1
            or sizes["num_expert_group"] != 1 or sizes["topk_group"] != 1
            or sizes["hidden_act"] != "silu" or sizes["tie_word_embeddings"]
            or sizes["num_key_value_heads"] != sizes["num_attention_heads"]
            or lin["num_heads"] != sizes["num_attention_heads"]
            or sizes["num_nextn_predict_layers"]):
        raise ValueError("not the block this builder writes down")
    dense, assumed = sizes["first_k_dense_replace"], sizes["kda"]
    return kimi_linear_48b_a3b(
        vocab_size=sizes["vocab_size"],
        d_model=sizes["hidden_size"],
        n_heads=sizes["num_attention_heads"],
        n_layers=sizes["num_hidden_layers"],
        dense_layers=dense,
        layer_types=tuple(
            ("kda" if kda else "latent") + (":swiglu" if i < dense else ":moe")
            for i, kda in enumerate(_kinds(sizes))
        ),
        d_ff=sizes["intermediate_size"],
        max_len=sizes["model_max_length"],
        norm_eps=sizes["rms_norm_eps"],
        n_experts=sizes["num_experts_routed"],
        experts_held=sizes["num_experts"],
        first_expert=sizes["first_expert"],
        top_k=sizes["num_experts_per_token"],
        d_expert=sizes["moe_intermediate_size"],
        shared_experts=sizes["num_shared_experts"],
        routed_scaling=float(sizes["routed_scaling_factor"]),
        latent=LatentConfig(
            q_rank=None, kv_rank=sizes["kv_lora_rank"],
            nope_dim=sizes["qk_nope_head_dim"],
            rope_dim=sizes["qk_rope_head_dim"], v_dim=sizes["v_head_dim"],
        ),
        kda=KDAConfig(
            heads=lin["num_heads"], key_dim=lin["head_dim"],
            value_dim=lin["head_dim"],
            conv_taps=lin["short_conv_kernel_size"],
            gate_rank=assumed["gate_rank"], chunk=assumed["chunk"],
        ),
        embed_init_std=sizes["init"]["embedding_std"],
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


def _conv(x, kernel):
    """``out_t = sum_j kernel[j] x_{t-(taps-1)+j}`` over ``x`` [S, C], zeros
    before the first token; ``kernel`` [taps, C], no bias."""
    taps, s = kernel.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1]), x.dtype), x])
    return sum(kernel[j] * padded[j:j + s] for j in range(taps))


def _delta_rule(q, k, v, g, beta, depart):
    """The recurrence TOKEN BY TOKEN, all heads: ``q``, ``k``, ``g``
    [S, h, d_k], ``v`` [S, h, d_v], ``beta`` [S, h]; the state [h, d_k,
    d_v] starts at zero. Returns ``o`` [S, h, d_v]."""
    def step(state, token):
        q_t, k_t, v_t, g_t, beta_t, at = token
        if depart == "independent_chunks":
            state = jnp.where(at % CHUNK_OF_DEPARTURE == 0, 0.0, state)
        decayed = jnp.exp(g_t)[:, :, None] * state
        read = jnp.einsum("hkv,hk->hv", decayed, k_t)
        write = v_t if depart == "no_delta" else v_t - read
        state = decayed + jnp.einsum(
            "hk,hv->hkv", k_t, beta_t[:, None] * write
        )
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    zero = jnp.zeros((k.shape[1], k.shape[2], v.shape[2]), jnp.float32)
    _, out = jax.lax.scan(
        step, zero, (q, k, v, g, beta, jnp.arange(q.shape[0]))
    )
    return out


def _kda(p, y, sizes: dict, r, depart):
    """One sequence ``y`` [S, D] through a Kimi Delta Attention mixer."""
    lin, eps = sizes["linear_attn_config"], sizes["rms_norm_eps"]
    heads, width = lin["num_heads"], lin["head_dim"]
    s = y.shape[0]

    def branch(name):
        x = r(y) @ r(p[f"{name}_proj"]["kernel"])
        if depart != "no_short_conv":
            x = _conv(x, p["conv"][name]["kernel"])
        return jax.nn.silu(x).reshape(s, heads, width)

    def unit(x):
        if depart == "no_qk_l2norm":
            return x
        return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)

    q, k, v = unit(branch("q")) * width ** -0.5, unit(branch("k")), branch("v")
    f = r(r(y) @ r(p["f_down"]["kernel"])) @ r(p["f_up"]["kernel"])
    g = -jnp.exp(p["decay"]["A_log"])[:, None] * jax.nn.softplus(
        f + p["decay"]["dt_bias"]
    ).reshape(s, heads, width)
    if depart == "scalar_decay":
        g = jnp.broadcast_to(g.mean(axis=-1, keepdims=True), g.shape)
    if depart == "decay_clamped":
        g = jnp.maximum(g, CLAMP)
    beta = jax.nn.sigmoid(r(y) @ r(p["beta"]["kernel"]))
    if depart == "beta_one":
        beta = jnp.ones_like(beta)
    o = _delta_rule(r(q), r(k), r(v), g, beta, depart)
    o = _rms_norm(o, p["gate_norm"]["scale"], eps)
    if depart != "no_output_gate":
        z = r(r(y) @ r(p["g_down"]["kernel"])) @ r(p["g_up"]["kernel"])
        o = o * jax.nn.sigmoid(z + p["g_up"]["bias"]).reshape(o.shape)
    return r(o.reshape(s, heads * width)) @ r(p["out"]["kernel"])


def _rope(x, theta: float):
    """``x`` [S, ..., R] rotated, feature i paired with i + R/2 (only the
    departure ``rotary_on_latent`` calls it)."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-np.arange(half, dtype=np.float32) / half)
    angle = np.arange(x.shape[0], dtype=np.float32)[:, None] * inv_freq
    angle = angle.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.asarray(np.cos(angle)), jnp.asarray(np.sin(angle))
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _latent_attention(p, y, sizes: dict, r, depart):
    """One sequence ``y`` [S, D]. Dense causal softmax attention in the
    expanded form, a head and ``QUERY_ROWS_AT_ONCE`` query rows at a time
    (``lax.map``: a block's scores over 16,384 keys are 33 MB in float32).
    Nothing is rotated."""
    eps, s = sizes["rms_norm_eps"], y.shape[0]
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    kv_rank = sizes["kv_lora_rank"]
    q = jnp.einsum("sd,dhk->hsk", r(y), r(p["q_up"]["kernel"]))
    down = r(y) @ r(p["kv_down"]["kernel"])
    c_kv, k_shared = down[:, :kv_rank], down[:, kv_rank:]
    c_kv = _rms_norm(c_kv, p["kv_norm"]["scale"], eps)
    kv = jnp.einsum("sr,rhk->hsk", r(c_kv), r(p["kv_up"]["kernel"]))
    if depart == "rotary_on_latent":
        theta = float(sizes["rope_theta"])
        q = jnp.concatenate([
            q[..., :nope], jnp.moveaxis(
                _rope(jnp.moveaxis(q[..., nope:], 0, 1), theta), 1, 0
            ),
        ], -1)
        k_shared = _rope(k_shared, theta)
    rows = min(QUERY_ROWS_AT_ONCE, s)
    if s % rows:
        raise ValueError(f"{s} positions in blocks of {rows}")
    scale = (nope + rope) ** -0.5
    key_at = np.arange(s)

    def one_head(qkv):
        q_h, kv_h = qkv                                       # [S, ·]
        k_h = jnp.concatenate([kv_h[:, :nope], k_shared], -1)
        v_h = kv_h[:, nope:]

        def one_block(args):
            q_b, r0 = args                                    # [rows, 192]
            see = key_at[None, :] <= r0 + np.arange(rows)[:, None]
            scores = (r(q_b) @ r(k_h).T) * scale
            probs = r(jax.nn.softmax(jnp.where(see, scores, -jnp.inf), -1))
            return r(probs @ r(v_h))

        blocks = jax.lax.map(
            one_block, (q_h.reshape(s // rows, rows, -1),
                        jnp.arange(0, s, rows)),
        )
        return blocks.reshape(s, -1)

    ctx = jax.lax.map(one_head, (q, kv))                      # [H, S, v]
    return jnp.einsum("hsk,hkd->sd", ctx, r(p["out"]["kernel"]))


def _swiglu(y, w_in, w_out, r):
    gate, up = jnp.split(r(y) @ r(w_in), 2, -1)
    return r(jax.nn.silu(gate) * up) @ r(w_out)


def _routed(p, bias, y, sizes: dict, r, depart):
    """The part of ``sum_j g_j E_j(y)`` that the HELD experts give, plus
    the shared expert: every token through each held expert, times a mask
    of the router's choice (no sort, no grouped matmul, no kernel). The
    router scores all ``num_experts_routed`` experts and keeps
    ``num_experts_per_token`` of them by ``score + bias``; what the absent
    ones would add is left out, as on the chip."""
    first, held = sizes["first_expert"], sizes["num_experts"]
    top_k = sizes["num_experts_per_token"]
    scores = jax.nn.sigmoid(y @ p["router"]["kernel"])
    # The k largest of score + bias; equal values go to the lower index.
    by_size = jnp.argsort(-(scores + bias), axis=-1, stable=True)
    mask = jnp.argsort(by_size, axis=-1) < top_k
    weights = jnp.where(mask, scores, 0.0)
    weights = weights / (weights.sum(axis=-1, keepdims=True) + 1e-6)
    if depart != "gates_times_one":
        weights = weights * sizes["routed_scaling_factor"]
    out = jnp.zeros_like(y)
    for e0 in range(0, held, EXPERTS_AT_ONCE):
        local = np.arange(e0, min(e0 + EXPERTS_AT_ONCE, held))
        h = jax.nn.silu(
            jnp.einsum("td,edf->tef", r(y), r(p["w_gate"][local]))
        ) * jnp.einsum("td,edf->tef", r(y), r(p["w_up"][local]))
        part = jnp.einsum("tef,efd->ted", r(h), r(p["w_down"][local]))
        out = out + jnp.einsum("ted,te->td", part, weights[:, first + local])
    shared = p["shared"]
    return out + _swiglu(y, shared["in"]["kernel"], shared["out"]["kernel"], r)


def _forward(params, ids, sizes: dict, trunk=None, depart=None):
    """Logits of the stack as ISSUE 44 writes it down (KDA as arXiv:
    2510.26692 parameterises it, latent attention in DeepSeek-V2's
    expanded form without its rotation, routing in the sigmoid-and-
    normalise form; written from the config and the papers: no network),
    straightforward float32 ``jax.numpy`` on the program's parameter tree,
    one sequence at a time:

        x = E[ids]
        per layer:  y = rms(x);  x += KDA(y)  or  latent attention(y)
                    z = rms(x);  x += FFN(z)   (dense, or held experts'
                                                part + shared expert)
        logits = rms(x) W_head

    ``trunk`` is None for the reference; a dtype rounds the blocks'
    weights and every matmul's inputs to it (decays, beta, the state,
    router, scores, norms and the head stay float32, as the configuration
    states), which shows what the tolerance refuses. ``depart`` names one
    of ``DEPARTURES``."""
    if depart is not None and depart not in DEPARTURES:
        raise ValueError(f"unknown departure {depart!r}")
    enc = params["params"]["encoder"]
    buffers = params.get("buffers", {}).get("encoder", {})
    eps, dense = sizes["rms_norm_eps"], sizes["first_k_dense_replace"]
    if trunk is None:
        r = lambda a: a  # noqa: E731
    else:
        r = lambda a: a.astype(trunk).astype(jnp.float32)  # noqa: E731
    head = params["params"]["lm_head"]["kernel"]              # [D, V]

    def one_sequence(row):
        x = r(enc["tok_embed"]["embedding"])[row]             # [S, D]
        for i, kda in enumerate(_kinds(sizes)):
            blk = enc[f"block_{i}"]
            if kda:
                y = _rms_norm(x, blk["ln_kda"]["scale"], eps)
                x = x + _kda(blk["kda"], y, sizes, r, depart)
            else:
                y = _rms_norm(x, blk["ln_attn"]["scale"], eps)
                x = x + _latent_attention(blk["attn"], y, sizes, r, depart)
            z = _rms_norm(x, blk["ln_mlp"]["scale"], eps)
            if i < dense:
                x = x + _swiglu(
                    z, blk["mlp_in"]["kernel"], blk["mlp_out"]["kernel"], r
                )
            else:
                bias = buffers[f"block_{i}"]["moe"]["expert_bias"]
                x = x + _routed(blk["moe"], bias, z, sizes, r, depart)
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


# ------------------------------------------------ operation and byte counts

def _layers_of(sizes: dict, kda: bool) -> int:
    return sum(1 for kind in _kinds(sizes) if kind == kda)


def _routed_layers(sizes: dict) -> int:
    return sizes["num_hidden_layers"] - sizes["first_k_dense_replace"]


def _matrix_params(sizes: dict) -> dict:
    """Matrix parameters a token touches, by where: a KDA mixer (q, k, v,
    both low-rank pairs, beta, the output), a latent attention's four
    projections, the dense FFN, a router, ONE expert (the shared expert is
    ``num_shared_experts`` of them), the head."""
    d, h = sizes["hidden_size"], sizes["num_attention_heads"]
    lin, rank = sizes["linear_attn_config"], sizes["kda"]["gate_rank"]
    wide = lin["num_heads"] * lin["head_dim"]
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    kv_rank, v_dim = sizes["kv_lora_rank"], sizes["v_head_dim"]
    return {
        "kda": (3 * d * wide + 2 * (d * rank + rank * wide)
                + d * lin["num_heads"] + wide * d),
        "latent": (d * h * (nope + rope) + d * (kv_rank + rope)
                   + kv_rank * h * (nope + v_dim) + h * v_dim * d),
        "mlp": 3 * d * sizes["intermediate_size"],
        "router": d * sizes["num_experts_routed"],
        "expert": 3 * d * sizes["moe_intermediate_size"],
        "head": d * sizes["vocab_size"],
    }


def n_params(sizes: dict) -> int:
    """Trained parameters held on this chip (``expert_bias`` is a buffer,
    ``num_experts_routed`` floats a routed layer, and is not among them)."""
    m, d = _matrix_params(sizes), sizes["hidden_size"]
    lin = sizes["linear_attn_config"]
    wide = lin["num_heads"] * lin["head_dim"]
    # Three convolutions, A_log, dt_bias, the gate's bias, the head norm.
    kda_vectors = (3 * lin["short_conv_kernel_size"] * wide
                   + lin["num_heads"] + 2 * wide + lin["head_dim"])
    return (
        _layers_of(sizes, True) * (m["kda"] + kda_vectors)
        + _layers_of(sizes, False) * (m["latent"] + sizes["kv_lora_rank"])
        + sizes["num_hidden_layers"] * 2 * d
        + sizes["first_k_dense_replace"] * m["mlp"]
        + _routed_layers(sizes) * (m["router"] + m["expert"] * (
            sizes["num_experts"] + sizes["num_shared_experts"]
        ))
        + 2 * m["head"] + d      # embedding and untied head, the final norm
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
    pairs = batch * traffic["seq_len"] * sizes["num_experts_per_token"]
    return (_routed_layers(sizes) * pairs * sizes["num_experts"]
            / sizes["num_experts_routed"])


def moe_flops_per_step(sizes: dict, traffic: dict, batch: int) -> float:
    """Operations of the grouped matmuls of one step, forward and
    backward: the pairs on held experts (``held_pairs_per_step``), three
    ``[D, F]`` matrices a row, 2 operations a multiply-add, 3 passes
    (forward, input gradient, weight gradient). The shared expert is a
    dense product, not a grouped one, and is not here."""
    per_row = 2 * _matrix_params(sizes)["expert"]
    return 3.0 * held_pairs_per_step(sizes, traffic, batch) * per_row


def _attention_pair_widths(sizes: dict):
    """Multiply-adds one (query, key) pair of one head costs: forward the
    score over ``nope + rope`` features and the mixing over ``v_head_dim``;
    backward the score again and ``dq``, ``dk`` at the first width, ``dp``
    and ``dv`` at the second (5 products for 2, as for equal widths)."""
    qk = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
    v = sizes["v_head_dim"]
    return qk + v, 3 * qk + 2 * v


def attention_flops_per_step(sizes: dict, traffic: dict, batch: int) -> float:
    """Operations of the latent layers' attention kernels in one step: the
    pairs that exist, ``S(S+1)/2`` a head, 2 operations a multiply-add, the
    widths of ``_attention_pair_widths`` forward and backward. Nothing
    recomputed is counted."""
    s = traffic["seq_len"]
    forward, backward = _attention_pair_widths(sizes)
    pairs = sizes["num_attention_heads"] * s * (s + 1) / 2
    return _layers_of(sizes, False) * batch * pairs * 2.0 * (
        forward + backward
    )


def kda_flops_per_token(sizes: dict) -> float:
    """Operations the recurrence costs a token, all heads of one layer,
    forward, whatever computes it: three products of ``2 d_k d_v`` (the
    state read at ``k``, the rank-one update, the state read at ``q``)
    and the decay's ``d_k d_v`` multiplies."""
    lin = sizes["linear_attn_config"]
    return lin["num_heads"] * 7.0 * lin["head_dim"] * lin["head_dim"]


def kda_flops_per_step(sizes: dict, traffic: dict, batch: int) -> float:
    """The delta rule of one step, forward and backward (twice the
    forward)."""
    tokens = batch * traffic["seq_len"]
    return 3.0 * _layers_of(sizes, True) * tokens * kda_flops_per_token(sizes)


def kda_bytes_per_step(sizes: dict, traffic: dict, batch: int) -> float:
    """Bytes the delta rule of one step has to move whatever the
    algorithm: ``q``, ``k``, ``v`` (compute dtype), ``g`` and ``beta``
    (float32) read and ``o`` written once forward; those and their
    gradients once backward, each in the dtype the program moves it in."""
    width = jnp.dtype(sizes["compute_dtype"]).itemsize
    lin = sizes["linear_attn_config"]
    wide = lin["num_heads"] * lin["head_dim"]
    once = width * 4 * wide + 4 * wide + 4 * lin["num_heads"]
    tokens = batch * traffic["seq_len"]
    return 2.0 * _layers_of(sizes, True) * tokens * once


def flops_per_sample(sizes: dict, traffic: dict) -> float:
    """Operations the forward and backward passes need for one sequence:
    3 x (2 x matrix parameters a token touches x tokens + the latent
    layers' scores and mixing over the pairs that exist + the delta rule's
    unavoidable count). A token touches its layer's mixer projections, the
    dense FFN or a router and the shared expert, and the head; the routed
    experts are counted by the pairs that landed on held ones
    (``held_pairs_per_step``). The embedding lookup is a gather; norms,
    convolutions and gates are not matmuls; nothing recomputed is counted
    (not the checkpointed forward, not the chunked form's extra
    products)."""
    s = traffic["seq_len"]
    m = _matrix_params(sizes)
    kda, latent = _layers_of(sizes, True), _layers_of(sizes, False)
    per_token = (
        kda * m["kda"] + latent * m["latent"]
        + sizes["first_k_dense_replace"] * m["mlp"]
        + _routed_layers(sizes) * (
            m["router"] + sizes["num_shared_experts"] * m["expert"]
        ) + m["head"]
    )
    batch = traffic["per_chip_batch"]
    experts = held_pairs_per_step(sizes, traffic, batch) / batch * m["expert"]
    forward, _ = _attention_pair_widths(sizes)
    attention = (latent * sizes["num_attention_heads"] * 2 * forward
                 * s * (s + 1) / 2)
    scan = kda * s * kda_flops_per_token(sizes)
    return 3.0 * (2 * (per_token * s + experts) + attention + scan)


def bytes_per_step(sizes: dict, traffic: dict, batch: int) -> float:
    """Bytes one chip's step has to move whatever the schedule: every
    parameter, its gradient and both AdamW moments read and written once
    in float32, and the batch read. Activations are left out, so this is
    a lower bound."""
    return 8.0 * 4 * n_params(sizes) + 4.0 * batch * traffic["seq_len"]
