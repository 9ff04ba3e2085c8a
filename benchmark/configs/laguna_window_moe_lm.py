"""Laguna style decoder LM (softmax attention over all earlier positions
in every fourth layer and over the last ``sliding_window`` in the others,
each kind with a head count and rotary positions of its own over shared
key-value heads, a sigmoid gate a head on attention's output; a dense FFN
in the leading layer and many small routed experts beside a shared one in
the others) as ONE CHIP'S SHARE of an expert-parallel deployment: how the
benchmark builds it through the program, its plain reference given the
same share (logits, and loss with gradients for the CPU tests), and its
operation and byte counts.

Sizes come from the configuration's JSON (the key names of the model's
``config.json``, ``model_type`` laguna). ``num_experts`` is how many
experts this chip HOLDS; ``num_experts_routed`` is the router's width and
``first_expert`` the first held one. A later configuration of the same
family adds a JSON that names this builder; nothing here knows a cell.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

# At the top, not in the functions: a program without this architecture
# (the parent of the PR that brought it) fails when the cell is loaded,
# before it starts a cluster or takes the chip.
from raydp_tpu.models.transformer import (
    CausalLM,
    WindowConfig,
    YarnScaling,
    laguna_xs_2,
)

# Program logits (bf16 trunk; float32 router, scores, gates, norms and
# head) against the float32 "highest" reference GIVEN THE SAME SHARE on ALL
# 16,384 positions of one seeded sequence, as the largest absolute
# difference over the largest reference magnitude
# (``harness.check_reference``), on the state the run's training left.
#
# What sets the error is the bf16 trunk: the plain reference with its
# trunk rounded to bfloat16 reads what the program reads (0.76-0.77%
# against 0.76-0.79% on the same two states). Measured on the chip at the
# published widths (PERF.md section 6, PR 38) after a 30 s run under the
# configuration's optimizer, twelve runs over twelve seeds: 0.72-0.85%.
# Departures on such states (two
# seeds where two numbers are given): a trunk in float8_e4m3 (the
# precision below the stated one) 6.0-6.6%, the sliding layers seeing
# every earlier position 8.6%, the full layers' frequencies without YaRN
# 12.2-14.3%, the whole head rotated in the full layers 26.8%, no gate a
# head 59%, the sliding layers with 48 heads 63%. 2.0% is 2.35 times the
# worst run and a third of the smallest reading of any departure (the
# float8 trunk's).
#
# One departure the check CANNOT tell from the program's own rounding,
# pinned by the float32 CPU tests (11% at the tiny size, program 6e-7):
# ``gates_times_one`` reads 0.76-0.82% (a chip holds 32 of 256 experts, so
# the routed part of a token's FFN output is an eighth of its pairs beside
# the whole shared expert, as PR 36 found for Xing4.0's share).
TOLERANCE = 0.02
UNSEEN_ON_THE_CHIP = ("gates_times_one",)
CHECK_ROWS = 1
# The reference runs in blocks so that 16,384 positions fit beside 11 GB
# of training state: attention a key-value head and this many query rows
# at a time, the experts this many at a time ([T, 4, F] float32), the head
# this many vocabulary rows.
QUERY_ROWS_AT_ONCE = 512
EXPERTS_AT_ONCE = 4
VOCAB_AT_ONCE = 4096

# Changes to the mathematics that ``_forward`` can make on request
# (``depart=``). The tests show that each reads above ``TOLERANCE`` at the
# tiny size in float32, PERF.md what each reads at the published widths.
DEPARTURES = (
    "no_window",            # sliding layers see every earlier position
    "rotary_on_every_dim",  # full layers rotate the whole head, not half
    "no_yarn",              # full layers: theta^(-i/32) as is, rotation x 1
    "no_head_gate",         # attention's output ungated
    "gates_times_one",      # routed scaling 1, not 2.5
    "window_heads_48",      # sliding layers with the full layers' 48 heads
)


def _kinds(sizes: dict):
    """(sliding?, query heads) of every layer, from the file's lists."""
    kinds = sizes["layer_types"]
    heads = sizes["num_attention_heads_per_layer"]
    if not (len(kinds) == len(heads) == len(sizes["mlp_layer_types"])
            == sizes["num_hidden_layers"]):
        raise ValueError("the per-layer lists do not name every layer")
    names = {"full_attention": False, "sliding_attention": True}
    return [(names[k], h) for k, h in zip(kinds, heads)]


def _head_counts(sizes: dict):
    """(full layers' query heads, sliding layers'), one count a kind."""
    full = {h for sliding, h in _kinds(sizes) if not sliding}
    slide = {h for sliding, h in _kinds(sizes) if sliding}
    if len(full) != 1 or len(slide) > 1:
        raise ValueError("one head count a kind of layer")
    return full.pop(), (slide.pop() if slide else 0)


def _dense_layers(sizes: dict) -> int:
    kinds = sizes["mlp_layer_types"]
    dense = sum(1 for k in kinds if k == "dense")
    if kinds != ["dense"] * dense + ["sparse"] * (len(kinds) - dense):
        raise ValueError("dense FFNs lead, routed layers follow")
    return dense


def _yarn(rope: dict) -> YarnScaling:
    if rope["rope_type"] != "yarn":
        raise ValueError("not the rotary scaling this builder writes down")
    return YarnScaling(
        factor=float(rope["factor"]),
        original_max_len=rope["original_max_position_embeddings"],
        beta_fast=float(rope["beta_fast"]),
        beta_slow=float(rope["beta_slow"]),
        attention_factor=float(rope["attention_factor"]),
    )


def model_config(sizes: dict):
    full_rope = sizes["rope_parameters"]["full_attention"]
    slide_rope = sizes["rope_parameters"]["sliding_attention"]
    if (sizes["model_type"] != "laguna" or sizes["attention_bias"]
            or not sizes["gating"] or sizes["tie_word_embeddings"]
            or sizes["moe_apply_router_weight_on_input"]
            or slide_rope["rope_type"] != "default"
            or sizes["shared_expert_intermediate_size"]
            != sizes["moe_intermediate_size"]
            or sizes["num_attention_heads"] != _head_counts(sizes)[0]):
        raise ValueError("not the block this builder writes down")
    head, dense = sizes["head_dim"], _dense_layers(sizes)
    full_heads, slide_heads = _head_counts(sizes)
    return laguna_xs_2(
        vocab_size=sizes["vocab_size"],
        d_model=sizes["hidden_size"],
        n_heads=full_heads,
        n_kv_heads=sizes["num_key_value_heads"],
        head_size=head,
        n_layers=sizes["num_hidden_layers"],
        dense_layers=dense,
        layer_types=tuple(
            ("window" if sliding else "attention")
            + (":swiglu" if i < dense else ":moe")
            for i, (sliding, _) in enumerate(_kinds(sizes))
        ),
        d_ff=sizes["intermediate_size"],
        max_len=sizes["max_position_embeddings"],
        norm_eps=sizes["rms_norm_eps"],
        rope_theta=float(full_rope["rope_theta"]),
        rotary_dim=int(head * full_rope["partial_rotary_factor"]),
        rope_yarn=_yarn(full_rope),
        window=WindowConfig(
            window=sizes["sliding_window"], n_heads=slide_heads,
            rope_theta=float(slide_rope["rope_theta"]),
            rotary_dim=int(head * slide_rope["partial_rotary_factor"]),
        ),
        n_experts=sizes["num_experts_routed"],
        experts_held=sizes["num_experts"],
        first_expert=sizes["first_expert"],
        top_k=sizes["num_experts_per_tok"],
        d_expert=sizes["moe_intermediate_size"],
        routed_scaling=float(sizes["moe_routed_scaling_factor"]),
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


def _inv_freq(dim: int, rope: dict, plain: bool) -> np.ndarray:
    """The ``dim / 2`` rotary frequencies of one kind of layer:
    ``theta^(-2i/dim)``, and under ``rope_type`` yarn (``transformers``'
    ``_compute_yarn_parameters`` transcribed) that where feature pair i
    turns more than ``beta_fast`` times over the original context, that
    over ``factor`` where fewer than ``beta_slow`` times, a linear ramp
    between."""
    base = float(rope["rope_theta"])
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rope["rope_type"] == "default" or plain:
        return extra.astype(np.float32)
    inter = extra / rope["factor"]
    original = rope["original_max_position_embeddings"]

    def correction_dim(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) / (
            2 * math.log(base)
        )

    low = max(math.floor(correction_dim(rope["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rope["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    keep = 1.0 - np.clip(
        (np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0, 1
    )
    return (inter * (1 - keep) + extra * keep).astype(np.float32)


def _rope(x, inv_freq, factor: float):
    """``x`` [S, H, R], the rotated part of every head; feature i pairs
    with i + R/2 (the published ``rotate_half``); cos and sin times
    ``factor`` (YaRN's ``attention_factor``)."""
    half = x.shape[-1] // 2
    angle = np.arange(x.shape[0], dtype=np.float32)[:, None] * inv_freq
    cos = jnp.asarray(np.cos(angle) * factor)[:, None]
    sin = jnp.asarray(np.sin(angle) * factor)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _positions(x, sizes: dict, sliding: bool, depart):
    """``x`` [S, H, 128] with the layer kind's positions turned in."""
    rope = sizes["rope_parameters"][
        "sliding_attention" if sliding else "full_attention"
    ]
    head = x.shape[-1]
    dim = int(head * rope["partial_rotary_factor"])
    if depart == "rotary_on_every_dim" and not sliding:
        dim = head
    plain = depart == "no_yarn"
    factor = 1.0 if plain else float(rope.get("attention_factor", 1.0))
    turned = _rope(x[..., :dim], _inv_freq(dim, rope, plain), factor)
    return jnp.concatenate([turned, x[..., dim:]], -1)


def _attention(p, y, sizes: dict, sliding: bool, r, depart):
    """One sequence ``y`` [S, D]. Dense softmax attention, a key-value
    head and ``QUERY_ROWS_AT_ONCE`` query rows at a time (``lax.map``: a
    group's scores over 16,384 keys are 200 MB in float32); query head h
    reads key-value head ``h // (H / 8)``. A sliding layer's rows see the
    last ``sliding_window`` keys, their own among them."""
    s, head = y.shape[0], sizes["head_dim"]
    kv_heads = sizes["num_key_value_heads"]
    w_q, w_o = p["q"]["kernel"], p["out"]["kernel"]
    w_g = p["gate"]["kernel"]
    if depart == "window_heads_48" and sliding:
        heads = _head_counts(sizes)[0]
        w_q, w_o, w_g = w_q[:, :heads], w_o[:heads], w_g[:, :heads]
    heads = w_q.shape[1]
    group = heads // kv_heads
    q = jnp.einsum("sd,dhk->shk", r(y), r(w_q))
    kv = jnp.einsum("sd,dchk->cshk", r(y), r(p["kv"]["kernel"]))
    q = _positions(q, sizes, sliding, depart)
    k, v = _positions(kv[0], sizes, sliding, depart), kv[1]
    window = sizes["sliding_window"] if (
        sliding and depart != "no_window"
    ) else None
    rows = min(QUERY_ROWS_AT_ONCE, s)
    if s % rows:
        raise ValueError(f"{s} positions in blocks of {rows}")
    # [kv head, block, group, rows, 128]
    q = q.reshape(s // rows, rows, kv_heads, group, head).transpose(
        2, 0, 3, 1, 4
    )
    k, v = k.transpose(1, 0, 2), v.transpose(1, 0, 2)         # [kv, S, 128]
    key_at = np.arange(s)

    def one_kv_head(qkv):
        q_h, k_h, v_h = qkv

        def one_block(args):
            q_b, r0 = args                                    # [group, rows, 128]
            at = r0 + np.arange(rows)[:, None]
            see = key_at[None, :] <= at
            if window is not None:
                see = see & (key_at[None, :] > at - window)
            scores = jnp.einsum("gqk,sk->gqs", r(q_b), r(k_h)) * head ** -0.5
            probs = r(jax.nn.softmax(jnp.where(see, scores, -jnp.inf), -1))
            return r(jnp.einsum("gqs,sk->gqk", probs, r(v_h)))

        starts = jnp.arange(0, s, rows)
        return jax.lax.map(one_block, (q_h, starts))          # [block, g, rows, k]

    ctx = jax.lax.map(one_kv_head, (q, k, v))       # [kv, block, g, rows, k]
    ctx = ctx.transpose(1, 3, 0, 2, 4).reshape(s, heads, head)
    if depart != "no_head_gate":
        ctx = ctx * jax.nn.sigmoid(r(y) @ r(w_g))[..., None]
    return jnp.einsum("shk,hkd->sd", r(ctx), r(w_o))


def _swiglu(y, w_in, w_out, r):
    gate, up = jnp.split(r(y) @ r(w_in), 2, -1)
    return r(jax.nn.silu(gate) * up) @ r(w_out)


def _routed(p, y, sizes: dict, r, depart):
    """The part of ``sum_j g_j E_j(y)`` that the HELD experts give, plus
    the shared expert: every token through each held expert, times a mask
    of the router's choice (no sort, no grouped matmul, no kernel). The
    router scores all ``num_experts_routed`` experts and keeps the
    ``num_experts_per_tok`` largest; what the absent ones would add is
    left out, as on the chip."""
    first, held = sizes["first_expert"], sizes["num_experts"]
    top_k = sizes["num_experts_per_tok"]
    scores = jax.nn.sigmoid(y @ p["router"]["kernel"])
    # The k largest; equal values go to the lower index.
    by_size = jnp.argsort(-scores, axis=-1, stable=True)
    mask = jnp.argsort(by_size, axis=-1) < top_k
    weights = jnp.where(mask, scores, 0.0)
    weights = weights / (weights.sum(axis=-1, keepdims=True) + 1e-6)
    if depart != "gates_times_one":
        weights = weights * sizes["moe_routed_scaling_factor"]
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
    """Logits of the stack as ISSUE 38 writes it down (attention and
    rotary scaling as ``transformers`` computes them for these config keys,
    routing in the sigmoid-and-normalise form; written from the config
    and the papers: no network), straightforward float32 ``jax.numpy`` on
    the program's parameter tree, one sequence at a time:

        x = E[ids]
        per layer:  y = rms(x);  x += W_o (sigmoid(y W_g) * attend(y))
                    z = rms(x);  x += FFN(z)   (dense, or held experts'
                                                part + shared expert)
        logits = rms(x) W_head

    ``trunk`` is None for the reference; a dtype rounds the blocks'
    weights and every matmul's inputs to it (router, scores, norms and the
    head stay float32, as the configuration states), which shows what the
    tolerance refuses. ``depart`` names one of ``DEPARTURES``."""
    if depart is not None and depart not in DEPARTURES:
        raise ValueError(f"unknown departure {depart!r}")
    enc = params["params"]["encoder"]
    eps, dense = sizes["rms_norm_eps"], _dense_layers(sizes)
    if trunk is None:
        r = lambda a: a  # noqa: E731
    else:
        r = lambda a: a.astype(trunk).astype(jnp.float32)  # noqa: E731
    head = params["params"]["lm_head"]["kernel"]              # [D, V]

    def one_sequence(row):
        x = r(enc["tok_embed"]["embedding"])[row]             # [S, D]
        for i, (sliding, _) in enumerate(_kinds(sizes)):
            blk = enc[f"block_{i}"]
            attn = blk["attn_window" if sliding else "attn"]
            y = _rms_norm(x, blk["ln_attn"]["scale"], eps)
            x = x + _attention(attn, y, sizes, sliding, r, depart)
            z = _rms_norm(x, blk["ln_mlp"]["scale"], eps)
            if i < dense:
                x = x + _swiglu(
                    z, blk["mlp_in"]["kernel"], blk["mlp_out"]["kernel"], r
                )
            else:
                x = x + _routed(blk["moe"], z, sizes, r, depart)
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

def _layers_of(sizes: dict, sliding: bool) -> int:
    return sum(1 for kind, _ in _kinds(sizes) if kind == sliding)


def _routed_layers(sizes: dict) -> int:
    return sizes["num_hidden_layers"] - _dense_layers(sizes)


def _matrix_params(sizes: dict) -> dict:
    """Matrix parameters a token touches, by where: one attention layer
    of either kind (q, k and v, the gate, the output), the dense FFN, a
    router, ONE expert (the shared expert is one more), the head."""
    d, head = sizes["hidden_size"], sizes["head_dim"]
    kv = 2 * sizes["num_key_value_heads"] * head
    full, slide = _head_counts(sizes)
    attention = lambda h: d * (2 * h * head + kv + h)  # noqa: E731
    return {
        "full": attention(full),
        "sliding": attention(slide),
        "mlp": 3 * d * sizes["intermediate_size"],
        "router": d * sizes["num_experts_routed"],
        "expert": 3 * d * sizes["moe_intermediate_size"],
        "head": d * sizes["vocab_size"],
    }


def _attention_params(sizes: dict) -> int:
    m = _matrix_params(sizes)
    return (_layers_of(sizes, False) * m["full"]
            + _layers_of(sizes, True) * m["sliding"])


def n_params(sizes: dict) -> int:
    """Trained parameters held on this chip."""
    m, d = _matrix_params(sizes), sizes["hidden_size"]
    return (
        _attention_params(sizes) + sizes["num_hidden_layers"] * 2 * d
        + _dense_layers(sizes) * m["mlp"]
        + _routed_layers(sizes) * (
            m["router"] + m["expert"] * (sizes["num_experts"] + 1)
        )
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
    pairs = batch * traffic["seq_len"] * sizes["num_experts_per_tok"]
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


def _pairs(sizes: dict, s: int, sliding: bool) -> float:
    """(query, key) pairs of one head that exist: ``S(S+1)/2`` over all
    earlier positions, ``S W - W(W-1)/2`` inside a window of W."""
    w = min(sizes["sliding_window"], s) if sliding else s
    return s * w - w * (w - 1) / 2


def _kernel_flops(sizes: dict, traffic: dict, batch: int,
                  sliding: bool) -> float:
    """Operations of one kind of layer's attention kernels in one step:
    the pairs that exist, 2 operations a multiply-add, two products of
    ``head_dim`` forward and five backward (3.5 x forward). Nothing
    recomputed is counted: not the checkpointed forward, not the scores
    the two backward kernels each rebuild."""
    heads = _head_counts(sizes)[sliding]
    pairs = heads * _pairs(sizes, traffic["seq_len"], sliding)
    return _layers_of(sizes, sliding) * batch * pairs * 2.0 * (
        7 * sizes["head_dim"]
    )


def attention_flops_per_step(sizes: dict, traffic: dict, batch: int) -> float:
    """The FULL layers' kernels (the Pallas calls under ``attn``)."""
    return _kernel_flops(sizes, traffic, batch, False)


def window_attention_flops_per_step(sizes: dict, traffic: dict,
                                    batch: int) -> float:
    """The sliding layers' kernels (the Pallas calls under
    ``attn_window``), over the pairs inside the window only: what no
    algorithm can avoid, so a kernel that masks what it could skip reads
    low against it."""
    return _kernel_flops(sizes, traffic, batch, True)


def flops_per_sample(sizes: dict, traffic: dict) -> float:
    """Operations the forward and backward passes need for one sequence:
    3 x (2 x matrix parameters a token touches x tokens + attention's
    scores and mixing over the pairs that exist, inside the window only
    for a sliding layer). A token touches its layer's attention
    projections, the dense FFN or a router and the shared expert, and the
    head; the routed experts are counted by the pairs that landed on held
    ones (``held_pairs_per_step``). The embedding lookup is a gather;
    norms and gates are not matmuls; nothing recomputed is counted."""
    s = traffic["seq_len"]
    m = _matrix_params(sizes)
    per_token = (
        _attention_params(sizes) + _dense_layers(sizes) * m["mlp"]
        + _routed_layers(sizes) * (m["router"] + m["expert"]) + m["head"]
    )
    batch = traffic["per_chip_batch"]
    experts = held_pairs_per_step(sizes, traffic, batch) / batch * m["expert"]
    full, slide = _head_counts(sizes)
    attention = 2 * 2 * sizes["head_dim"] * (
        _layers_of(sizes, False) * full * _pairs(sizes, s, False)
        + _layers_of(sizes, True) * slide * _pairs(sizes, s, True)
    )
    return 3.0 * (2 * (per_token * s + experts) + attention)


def bytes_per_step(sizes: dict, traffic: dict, batch: int) -> float:
    """Bytes one chip's step has to move whatever the schedule: every
    parameter, its gradient and both AdamW moments read and written once
    in float32, and the batch read. Activations are left out, so this is
    a lower bound."""
    return 8.0 * 4 * n_params(sizes) + 4.0 * batch * traffic["seq_len"]
