"""SDAR style decoder LM trained by BLOCK DIFFUSION (grouped-query
attention with a head size of its own and an RMSNorm a head on q and k,
the whole head rotated; many small routed experts, softmax scores
renormalised over the selected, no shared expert; a noised and a clean copy
of every sequence in one pass of 2·S positions under a block-structured
mask, a weighted loss over the masked tokens) as ONE CHIP'S SHARE of an
expert-parallel deployment: how the benchmark builds it through the
program, its plain reference given the same share AND THE SAME NOISE
(logits, and loss with gradients for the CPU tests), and its operation and
byte counts.

Sizes come from the configuration's JSON (the key names of the model's
``config.json``, ``model_type`` sdar_moe). ``num_experts`` is how many
experts this chip HOLDS; ``num_experts_routed`` is the router's width and
``first_expert`` the first held one; the group ``diffusion`` holds the
objective's sizes (block length, mask id, smallest noise level). The group ``init``
gives the embedding's std and the depth the residual outputs are scaled
for; the deployment places each layer's experts on its chips by load,
where the weights are drawn (:func:`placed_share`). A later
configuration of the same family adds a JSON that names this builder;
nothing here knows a cell.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# At the top, not in the functions: a program without this objective (the
# parent of the PR that brought it) fails when the cell is loaded, before it
# starts a cluster or takes the chip.
from raydp_tpu.models import stats
from raydp_tpu.models.blockdiff import BlockDiffusionConfig, BlockDiffusionLM
from raydp_tpu.models.transformer import sdar_30b_a3b

# Program logits (bf16 trunk; float32 router, probabilities, gates, norms
# and head) against the float32 "highest" reference GIVEN THE SAME SHARE AND
# THE SAME NOISE on ALL 8,192 noised positions of one seeded pair of the
# timed shape, as the largest absolute difference over the largest
# reference magnitude (``harness.check_reference``), on the state the run's
# training left. On the chip (PERF.md section 6, PR 47; the weights as the
# configuration draws them, the residual outputs depth-scaled) the program
# reads 0.353-0.453% over five runs and the reference with a bfloat16
# trunk 0.399% on a state where the program reads 0.383%: the trunk's
# rounding sets the error. The precision below the stated one, a float8
# trunk, reads 3.05%, and the nearest departure that is seen,
# ``no_qk_norm``, 2.91%: 1.2% is 2.6 times the program's largest reading
# and 2.4 times under the nearest departure's. (The readings belong to
# this init: at the library's plain one the blocks weigh as much as the
# embedding and everything reads larger, the program 0.52-0.67%, a float8
# trunk 51-68%.) One departure reads what the program reads, because the
# routed path adds little beside a unit embedding at this init: it is
# listed, and the CPU tests see it in float32.
TOLERANCE = 0.012
UNSEEN_ON_THE_CHIP = ("gates_not_renormalised",)
CHECK_ROWS = 1
# The reference runs in blocks so that a 16,384-position pair fits beside
# 10 GB of training state: attention a key-value head and this many query
# rows at a time ([8, 512, 16384] float32 scores are 268 MB), the experts
# this many at a time, the head this many vocabulary rows.
QUERY_ROWS_AT_ONCE = 512
EXPERTS_AT_ONCE = 4
VOCAB_AT_ONCE = 4096

# Changes to the mathematics that ``_forward`` can make on request
# (``depart=``). The tests show that each reads above ``TOLERANCE`` at the
# tiny size in float32, PERF.md what each reads at the published widths.
DEPARTURES = (
    "plain_causal_pair",            # the 2·S positions under a causal mask
    "noised_sees_own_clean_block",  # noised -> clean: b(j) <= b(i), the leak
    "clean_sees_noised",            # clean -> noised: b(j) <= b(i), not never
    "own_block_causal",             # noised -> noised: own block, j <= i only
    "block_8",                      # blocks of 2·L tokens
    "positions_run_on",             # p = 0 … 2S-1, not 0 … S-1 twice
    "no_qk_norm",                   # q and k as projected
    "gates_not_renormalised",       # the selected probabilities as they are
    "trunk_float8",                 # the precision below the stated one
)


def _diffusion(sizes: dict) -> BlockDiffusionConfig:
    group = sizes["diffusion"]
    return BlockDiffusionConfig(
        block_length=group["block_length"], mask_id=group["mask_token_id"],
        t_min=group["t_min"],
    )


def model_config(sizes: dict):
    if (sizes["model_type"] != "sdar_moe" or sizes["attention_bias"]
            or not sizes["norm_topk_prob"] or sizes["tie_word_embeddings"]
            or sizes["hidden_act"] != "silu" or sizes["mlp_only_layers"]
            or sizes["decoder_sparse_step"] != 1
            or sizes["use_sliding_window"] or sizes["rope_scaling"]
            or sizes["diffusion"]["schedule"] != "linear"
            or not 0 <= sizes["diffusion"]["mask_token_id"]
            < sizes["vocab_size"]):
        raise ValueError("not the block this builder writes down")
    return sdar_30b_a3b(
        vocab_size=sizes["vocab_size"],
        d_model=sizes["hidden_size"],
        n_heads=sizes["num_attention_heads"],
        n_kv_heads=sizes["num_key_value_heads"],
        head_size=sizes["head_dim"],
        n_layers=sizes["num_hidden_layers"],
        max_len=sizes["max_position_embeddings"],
        norm_eps=sizes["rms_norm_eps"],
        rope_theta=float(sizes["rope_theta"]),
        n_experts=sizes["num_experts_routed"],
        experts_held=sizes["num_experts"],
        first_expert=sizes["first_expert"],
        top_k=sizes["num_experts_per_tok"],
        d_expert=sizes["moe_intermediate_size"],
        diffusion=_diffusion(sizes),
        embed_init_std=sizes["init"]["embedding_std"],
        attention_impl=sizes["attention_impl"],
        remat=sizes.get("remat", False),
        dtype=jnp.dtype(sizes["compute_dtype"]),
        param_dtype=jnp.dtype(sizes["param_dtype"]),
    )


def balanced_placement(load, shares: int):
    """Which experts each of ``shares`` chips holds, from the pairs each
    expert received (``load`` [E]): the experts heaviest first, each onto
    the chip whose experts so far received least and which still has room
    for one (``E / shares`` a chip), as an expert-parallel load balancer
    places them from observed loads. Returns the experts ordered by chip,
    chip 0's first, each chip's by index: ``order`` [E]. Traceable."""
    experts = load.shape[0]
    room = experts // shares
    heaviest_first = jnp.argsort(-load, stable=True)

    def place(i, carry):
        total, held, chip_of = carry
        expert = heaviest_first[i]
        chip = jnp.argmin(jnp.where(held < room, total, jnp.inf))
        return (total.at[chip].add(load[expert]), held.at[chip].add(1),
                chip_of.at[expert].set(chip))

    _, _, chip_of = jax.lax.fori_loop(0, experts, place, (
        jnp.zeros(shares, jnp.float32), jnp.zeros(shares, jnp.int32),
        jnp.zeros(experts, jnp.int32),
    ))
    return jnp.argsort(chip_of, stable=True)


def scale_residual_outputs(variables, scale: float):
    """``variables`` as ``model.init`` returns them with the two matrices
    that write into the residual stream, attention's ``out`` and the
    experts' ``w_down``, times ``scale``: the depth-scaled init of GPT-2
    and Megatron-LM (``(2 · layers) ** -0.5`` of the plain init), which the
    library's stacks do not apply."""
    from flax.core import meta
    from flax.traverse_util import flatten_dict, unflatten_dict

    params = flatten_dict(dict(variables["params"]))
    for path, leaf in params.items():
        if path[-3:] == ("attn", "out", "kernel") or path[-2:] == (
                "moe", "w_down"):
            params[path] = meta.replace_boxed(leaf, meta.unbox(leaf) * scale)
    return {**variables, "params": unflatten_dict(params)}


def place_experts(variables, sown, held: int, first: int):
    """``variables`` as ``model.init`` returns them, with every routed
    layer's router reading the SAME columns in another order: the order
    :func:`balanced_placement` gives from the pairs that layer's experts
    received in one pass (``sown``: a step's ``moe_stats``, its
    ``expert_tokens``), turned so that chip 0's experts are the columns
    ``first … first + held - 1`` this chip holds. An expert's column is
    its name: the model is the one ``init`` drew, and which of its experts
    this chip holds is the deployment's to say."""
    from flax.core import meta
    from flax.traverse_util import flatten_dict, unflatten_dict

    params = flatten_dict(dict(variables["params"]))
    for path, load in flatten_dict(dict(sown)).items():
        if path[-1] != "expert_tokens":
            continue
        at = path[:-1] + ("router", "kernel")
        order = jnp.roll(
            balanced_placement(load, load.shape[0] // held), first
        )
        params[at] = meta.replace_boxed(
            params[at], meta.unbox(params[at])[:, order]
        )
    return {**variables, "params": unflatten_dict(params)}


def placed_share(cfg, out_scale: float = 1.0) -> BlockDiffusionLM:
    """``BlockDiffusionLM(cfg)`` as one chip of the configuration's
    deployment: ``init`` draws the weights as the model does, scales the
    residual outputs by ``out_scale`` (:func:`scale_residual_outputs`),
    runs ONE training pass of those weights over the ids it was given (a
    noised pair of the first batch) and places each layer's experts on
    the chips by the loads of that pass (:func:`place_experts`). The step
    is ``BlockDiffusionLM``'s. (The scale is closed over and no field: a
    module the harness loads by path cannot declare one.)"""

    class PlacedShare(BlockDiffusionLM):
        def init(self, rngs, ids, **kwargs):
            variables = scale_residual_outputs(
                super().init(rngs, ids, **kwargs), out_scale
            )
            key = rngs["params"] if isinstance(rngs, dict) else rngs
            _, sown = self.apply(
                {"params": variables["params"]}, ids, deterministic=False,
                rngs={"noise": key}, mutable=[stats.STATS],
            )
            moe = self.cfg.moe_config()
            return place_experts(
                variables, sown[stats.STATS], moe.held, moe.first_expert
            )

    return PlacedShare(cfg)


def deployed_model(sizes: dict) -> BlockDiffusionLM:
    """The model as the configuration's ``init`` and ``deployment`` groups
    say: residual outputs scaled for ``init.depth_scaled_outputs`` layers
    where the file gives them, the experts placed by load."""
    depth = sizes["init"].get("depth_scaled_outputs")
    return placed_share(
        model_config(sizes), (2.0 * depth) ** -0.5 if depth else 1.0
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
    """Arguments of ``JAXEstimator`` for this configuration. The host
    sends the S clean ids of a sequence; the model noises them on the
    device. ``aux_losses`` is on for what the step sows (the routing
    counts and the masked tokens; both loss weights are 0: the
    configuration has no auxiliary loss)."""
    return dict(
        model=deployed_model(sizes),
        optimizer=_optimizer(sizes["optimizer"]),
        loss="blockdiff_ce",
        self_supervised=True,
        aux_losses=True,
        feature_columns=[f"t{i}" for i in range(traffic["seq_len"])],
        label_column=None,
        feature_dtype=np.int32,
    )


def check_noise(sizes: dict, traffic: dict, seed: int):
    """``(ids [R, S], masked [R, S] bool, t [R, S/L])`` of the check's
    pair: seeded ids over the vocabulary and a seeded noise of the stated
    distribution (``t ~ U[t_min, 1]`` a block, ``m ~ Bernoulli(t)`` a
    token), drawn on the host so that program and reference see the same."""
    diff = sizes["diffusion"]
    s, length = traffic["seq_len"], diff["block_length"]
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, sizes["vocab_size"], size=(CHECK_ROWS, s))
    t = diff["t_min"] + rng.random((CHECK_ROWS, s // length)) * (
        1.0 - diff["t_min"]
    )
    masked = rng.random((CHECK_ROWS, s)) < np.repeat(t, length, axis=1)
    return ids.astype(np.int32), masked, t.astype(np.float32)


def check_batch(sizes: dict, traffic: dict, seed: int) -> np.ndarray:
    """One pair ``[xᵗ ; x⁰]`` [1, 2·S] of the timed shape, as the
    evaluation mode of the model takes it."""
    ids, masked, _ = check_noise(sizes, traffic, seed)
    noised = np.where(masked, sizes["diffusion"]["mask_token_id"], ids)
    return np.concatenate([noised, ids], axis=1).astype(np.int32)


# ------------------------------------------------------ plain reference

def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, positions, theta: float):
    """``x`` [P, H, D] rotated over the whole head by ``positions`` [P];
    feature i pairs with i + D/2 (the published ``rotate_half``)."""
    half = x.shape[-1] // 2
    inv_freq = 1.0 / theta ** (np.arange(half, dtype=np.float64) / half)
    angle = positions.astype(np.float64)[:, None] * inv_freq
    cos = jnp.asarray(np.cos(angle), jnp.float32)[:, None]
    sin = jnp.asarray(np.sin(angle), jnp.float32)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _sees(at, key, n: int, length: int, depart):
    """The pair mask from its definition, by index arithmetic: ``at`` [R,
    1] query and ``key`` [1, K] key positions (jax arrays) of the 2n laid out [noised ;
    clean], ``b(i) = (i mod n) // length``. Noised -> noised: the same
    block. Noised -> clean: a block strictly before. Clean -> noised:
    never. Clean -> clean: the same block or one before."""
    if depart == "plain_causal_pair":
        return key <= at
    q_clean, k_clean = at >= n, key >= n
    q_in, k_in = at % n, key % n
    q_b, k_b = q_in // length, k_in // length
    own = k_b == q_b
    if depart == "own_block_causal":
        own = own & (k_in <= q_in)
    before = k_b <= q_b if depart == "noised_sees_own_clean_block" else (
        k_b < q_b
    )
    leak = k_b <= q_b if depart == "clean_sees_noised" else (
        jnp.zeros_like(own)
    )
    return jnp.where(
        q_clean, jnp.where(k_clean, k_b <= q_b, leak),
        jnp.where(k_clean, before, own),
    )


def _attention(p, y, sizes: dict, r, depart):
    """One pair ``y`` [2S, D]. Dense masked softmax attention, a key-value
    head and ``QUERY_ROWS_AT_ONCE`` query rows at a time (``lax.map``);
    query head h reads key-value head ``h // (H / Hkv)``."""
    pairs, head = y.shape[0], sizes["head_dim"]
    n = pairs // 2
    heads, kv_heads = sizes["num_attention_heads"], (
        sizes["num_key_value_heads"]
    )
    group, eps = heads // kv_heads, sizes["rms_norm_eps"]
    length = sizes["diffusion"]["block_length"] * (
        2 if depart == "block_8" else 1
    )
    q = jnp.einsum("sd,dhk->shk", r(y), r(p["q"]["kernel"]))
    kv = jnp.einsum("sd,dchk->cshk", r(y), r(p["kv"]["kernel"]))
    k, v = kv[0], kv[1]
    if depart != "no_qk_norm":
        q = _rms_norm(q, p["q_norm"]["scale"], eps)
        k = _rms_norm(k, p["k_norm"]["scale"], eps)
    positions = np.arange(pairs) if depart == "positions_run_on" else (
        np.arange(pairs) % n
    )
    theta = float(sizes["rope_theta"])
    q, k = _rope(q, positions, theta), _rope(k, positions, theta)
    rows = min(QUERY_ROWS_AT_ONCE, pairs)
    if pairs % rows:
        raise ValueError(f"{pairs} positions in blocks of {rows}")
    # [kv head, block, group, rows, head]
    q = q.reshape(pairs // rows, rows, kv_heads, group, head).transpose(
        2, 0, 3, 1, 4
    )
    k, v = k.transpose(1, 0, 2), v.transpose(1, 0, 2)         # [kv, 2S, head]
    key_at = jnp.arange(pairs)[None, :]

    def one_kv_head(qkv):
        q_h, k_h, v_h = qkv

        def one_block(args):
            q_b, r0 = args                            # [group, rows, head]
            see = _sees(
                r0 + jnp.arange(rows)[:, None], key_at, n, length, depart
            )
            scores = jnp.einsum("gqk,sk->gqs", r(q_b), r(k_h)) * head ** -0.5
            probs = r(jax.nn.softmax(jnp.where(see, scores, -jnp.inf), -1))
            return r(jnp.einsum("gqs,sk->gqk", probs, r(v_h)))

        starts = jnp.arange(0, pairs, rows)
        return jax.lax.map(one_block, (q_h, starts))  # [block, g, rows, head]

    ctx = jax.lax.map(one_kv_head, (q, k, v))
    ctx = ctx.transpose(1, 3, 0, 2, 4).reshape(pairs, heads, head)
    return jnp.einsum("shk,hkd->sd", r(ctx), r(p["out"]["kernel"]))


def _routed(p, y, sizes: dict, r, depart):
    """The part of ``sum_j g_j E_j(y)`` that the HELD experts give: every
    token through each held expert, times a mask of the router's choice
    (no sort, no grouped matmul, no kernel). The router's softmax is over
    all ``num_experts_routed`` experts, the ``num_experts_per_tok`` largest
    probabilities are selected (no selection bias) and divided by their
    sum; what the absent ones would add is left out, as on the chip."""
    first, held = sizes["first_expert"], sizes["num_experts"]
    top_k = sizes["num_experts_per_tok"]
    scores = jax.nn.softmax(y @ p["router"]["kernel"], axis=-1)
    # The k largest; equal values go to the lower index.
    by_size = jnp.argsort(-scores, axis=-1, stable=True)
    mask = jnp.argsort(by_size, axis=-1) < top_k
    weights = jnp.where(mask, scores, 0.0)
    if depart != "gates_not_renormalised":
        weights = weights / (weights.sum(axis=-1, keepdims=True) + 1e-6)
    out = jnp.zeros_like(y)
    for e0 in range(0, held, EXPERTS_AT_ONCE):
        local = np.arange(e0, min(e0 + EXPERTS_AT_ONCE, held))
        h = jax.nn.silu(
            jnp.einsum("td,edf->tef", r(y), r(p["w_gate"][local]))
        ) * jnp.einsum("td,edf->tef", r(y), r(p["w_up"][local]))
        part = jnp.einsum("tef,efd->ted", r(h), r(p["w_down"][local]))
        out = out + jnp.einsum("ted,te->td", part, weights[:, first + local])
    return out


def _forward(params, pair, sizes: dict, trunk=None, depart=None):
    """Logits of the NOISED half of each pair, as ISSUE 47 writes the step
    down, straightforward float32 ``jax.numpy`` on the program's parameter
    tree, one pair at a time:

        x = E[[xᵗ ; x⁰]]                                   [2S, D]
        per layer:  y = rms(x);  x += W_o attend(y)   (the pair mask)
                    z = rms(x);  x += held experts' part of FFN(z)
        logits = rms(x[:S]) W_head

    All 2S rows run every layer. ``trunk`` is None for the reference; a
    dtype rounds the blocks' weights and every matmul's inputs to it
    (router, probabilities, norms and the head stay float32, as the
    configuration states). ``depart`` names one of ``DEPARTURES``."""
    if depart is not None and depart not in DEPARTURES:
        raise ValueError(f"unknown departure {depart!r}")
    if depart == "trunk_float8":
        trunk, depart = jnp.float8_e4m3fn, None
    enc = params["params"]["encoder"]
    eps = sizes["rms_norm_eps"]
    if trunk is None:
        r = lambda a: a  # noqa: E731
    else:
        r = lambda a: a.astype(trunk).astype(jnp.float32)  # noqa: E731
    head = params["params"]["lm_head"]["kernel"]              # [D, V]

    def one_pair(row):
        x = r(enc["tok_embed"]["embedding"])[row]             # [2S, D]
        for i in range(sizes["num_hidden_layers"]):
            blk = enc[f"block_{i}"]
            y = _rms_norm(x, blk["ln_attn"]["scale"], eps)
            x = x + _attention(blk["attn"], y, sizes, r, depart)
            z = _rms_norm(x, blk["ln_mlp"]["scale"], eps)
            x = x + _routed(blk["moe"], z, sizes, r, depart)
        x = _rms_norm(x[:x.shape[0] // 2], enc["ln_final"]["scale"], eps)
        return jnp.concatenate([
            x @ head[:, v0:v0 + VOCAB_AT_ONCE]
            for v0 in range(0, head.shape[1], VOCAB_AT_ONCE)
        ], axis=-1)

    return jnp.stack([one_pair(row) for row in pair])


def reference_logits(params, pair, sizes: dict, trunk=None, depart=None):
    """[R, S, V] from pairs [R, 2·S] as :func:`check_batch` makes them."""
    with jax.default_matmul_precision("highest"):
        return _forward(params, pair, sizes, trunk, depart)


def reference_loss_and_grads(params, ids, masked, t, sizes: dict):
    """The block-diffusion loss of clean ids [R, S] under a given noise
    (``masked`` [R, S], ``t`` [R, S/L]), ``(1 / (R·S)) Σ_i (m_i / t_b(i)) ·
    (logsumexp(ℓ_i) − ℓ_i[x⁰_i])``, no shift, and its gradients with
    respect to ``params`` (the CPU tests compare the program's)."""
    diff = sizes["diffusion"]
    ids, masked = jnp.asarray(ids), jnp.asarray(masked)
    pair = jnp.concatenate(
        [jnp.where(masked, diff["mask_token_id"], ids), ids], axis=1
    )
    weight = masked / jnp.repeat(jnp.asarray(t), diff["block_length"], axis=1)

    def loss(p):
        logp = jax.nn.log_softmax(_forward(p, pair, sizes), axis=-1)
        own = jnp.take_along_axis(logp, ids[..., None], axis=-1)[..., 0]
        return -jnp.sum(weight * own) / ids.size

    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss)(params)


# ------------------------------------------------ operation and byte counts

def _matrix_params(sizes: dict) -> dict:
    """Matrix parameters a position touches, by where: one layer's
    attention (q, k and v, the output), a router, ONE expert, the head."""
    d, head = sizes["hidden_size"], sizes["head_dim"]
    q = sizes["num_attention_heads"] * head
    kv = 2 * sizes["num_key_value_heads"] * head
    return {
        "attention": d * (2 * q + kv),
        "router": d * sizes["num_experts_routed"],
        "expert": 3 * d * sizes["moe_intermediate_size"],
        "head": d * sizes["vocab_size"],
    }


def n_params(sizes: dict) -> int:
    """Trained parameters held on this chip."""
    m, d = _matrix_params(sizes), sizes["hidden_size"]
    layer = (
        m["attention"] + 2 * sizes["head_dim"] + 2 * d + m["router"]
        + m["expert"] * sizes["num_experts"]
    )
    # Embedding and untied head, the final norm.
    return sizes["num_hidden_layers"] * layer + 2 * m["head"] + d


def pair_positions_per_step(traffic: dict, batch: int) -> int:
    """Rows a step sends through every layer: both copies of a sequence."""
    return 2 * batch * traffic["seq_len"]


def held_pairs_per_step(sizes: dict, traffic: dict, batch: int) -> float:
    """(position, expert) pairs of one step that landed on experts held
    here, over all layers: what the program counted on the device over its
    last epoch (gauge ``moe/held_pairs_per_step``); before the first
    epoch, the expectation at uniform routing, ``2·S·k·held/routed`` a
    layer and sequence."""
    from raydp_tpu.utils.profiling import metrics

    counted = metrics.gauge_value("moe/held_pairs_per_step")
    if counted:
        return float(counted)
    pairs = pair_positions_per_step(traffic, batch) * (
        sizes["num_experts_per_tok"]
    )
    return (sizes["num_hidden_layers"] * pairs * sizes["num_experts"]
            / sizes["num_experts_routed"])


def moe_flops_per_step(sizes: dict, traffic: dict, batch: int) -> float:
    """Operations of the grouped matmuls of one step, forward and
    backward: the pairs on held experts, three ``[D, F]`` matrices a row, 2
    operations a multiply-add, 3 passes (forward, input gradient, weight
    gradient)."""
    per_row = 2 * _matrix_params(sizes)["expert"]
    return 3.0 * held_pairs_per_step(sizes, traffic, batch) * per_row


def mask_pairs(sizes: dict, s: int) -> float:
    """(query, key) pairs of one head INSIDE the pair mask: ``S·L`` own
    block, ``S(S−L)/2`` noised -> clean, ``S(S+L)/2`` clean -> clean."""
    return float(s) * s + float(s) * sizes["diffusion"]["block_length"]


def attention_flops_per_step(sizes: dict, traffic: dict, batch: int) -> float:
    """Operations of attention over the pairs inside the mask in one step:
    2 operations a multiply-add, two products of ``head_dim`` forward and
    five backward (3.5 x forward). What no algorithm can avoid, so a kernel
    that computes tiles it could skip reads low; nothing recomputed is
    counted."""
    pairs = sizes["num_attention_heads"] * mask_pairs(
        sizes, traffic["seq_len"]
    )
    return sizes["num_hidden_layers"] * batch * pairs * 2.0 * (
        7 * sizes["head_dim"]
    )


def flops_per_sample(sizes: dict, traffic: dict) -> float:
    """Operations the forward and backward passes need for one sequence:
    3 x (2 x matrix parameters a position touches x positions + attention's
    scores and mixing over the pairs inside the mask). Both copies (2·S
    positions) touch every layer's attention projections and router; the S
    noised positions touch the head; the routed experts are counted by the
    pairs that landed on held ones. The embedding lookup is a gather;
    norms and gates are not matmuls; nothing recomputed is counted."""
    s = traffic["seq_len"]
    m = _matrix_params(sizes)
    layers = sizes["num_hidden_layers"]
    batch = traffic["per_chip_batch"]
    matrices = layers * (m["attention"] + m["router"]) * 2 * s + m["head"] * s
    experts = held_pairs_per_step(sizes, traffic, batch) / batch * m["expert"]
    attention = 2 * 2 * sizes["head_dim"] * layers * (
        sizes["num_attention_heads"] * mask_pairs(sizes, s)
    )
    return 3.0 * (2 * (matrices + experts) + attention)


def bytes_per_step(sizes: dict, traffic: dict, batch: int) -> float:
    """Bytes one chip's step has to move whatever the schedule: every
    parameter, its gradient and both AdamW moments read and written once
    in float32, and the batch read. Activations are left out, so this is
    a lower bound."""
    return 8.0 * 4 * n_params(sizes) + 4.0 * batch * traffic["seq_len"]
