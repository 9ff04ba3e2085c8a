"""Mellum 2 style decoder LM (grouped-query softmax attention over the
last ``sliding_window`` positions in three layers of four and over all
earlier positions, under YaRN, in the fourth; 64 small routed experts in
every layer, 8 a token by renormalised softmax probability, no shared
expert) as the WHOLE group of chips that shares each layer: how the
benchmark builds it through the program on a mesh — every expert, a
quarter a chip, with the expert exchange; the whole vocabulary, a quarter
a chip — its plain reference, which knows no mesh and no share (logits,
and loss with gradients for the CPU tests), and its operation and byte
counts.

Sizes come from the configuration's JSON (the key names of the model's
``config.json``, ``model_type`` mellum). A later configuration of the
same family adds a JSON that names this builder; nothing here knows a
cell.
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
    mellum2_12b_a2_5b,
)

# Program logits over the four-chip mesh (bf16 trunk; float32 router,
# gates, norms and head) against the float32 "highest" reference of the
# UNCUT layer stack on ALL 4,096 positions of one seeded sequence, as the
# largest absolute difference over the largest reference magnitude
# (``harness.check_reference``), on the state the run's training left.
#
# Two things set the error. The bf16 trunk's rounding, about 0.5% on every
# token; and, since the expert stacks are drawn at ``init.expert_stack_gain``
# 4 so that the routed sum reaches the logits, the tokens whose EIGHTH
# choice the rounding moves to their ninth expert (about one in twenty a
# layer): their logits move by one small gate's expert, and the largest of
# them is what the check reads. Measured on the chip at the published
# widths (PERF.md section 6, PR 53; seed 101 after a 12 s run under the
# configuration's optimizer, five rows of tokens): 0.91-1.29%. Departures
# on that state: the gates as they are 2.91%, chip 0's experts alone (the
# exchange left out) 7.15%, a trunk in float8_e4m3 (the precision below
# the stated one) 10.2%; at gain 1, where the routed sum is 1/64 of this
# and moves none of them, the full layer's frequencies without YaRN
# 13.8%, the sliding layers seeing every earlier position 16.0%, the
# sliding layers under YaRN too 25.8%. 2.0% is 1.55 times the worst row
# and 1.45 times under the nearest departure: the middle of the two by
# ratio (1.94%). Why not the per-expert fan (gain 8): the same state then
# reads 11.9% for the program itself, above the float8 trunk's reading,
# and 27.8% / 54.9% for the two departures; at gain 5, 2.13% / 5.8% /
# 14.2%; at gain 1, 0.58% / 0.583% / 0.590% (nothing inside a routed layer
# seen). The flipped choice's reading and the two departures' all scale
# with the routed sum, so their ratio (2.3-2.7) is what a gain can buy.
TOLERANCE = 0.02
CHECK_ROWS = 1
# The reference runs in blocks so that 4,096 positions fit beside the
# training state: attention a key-value head and this many query rows at a
# time, the experts this many at a time ([T, 8, F] float32), the head this
# many vocabulary columns.
QUERY_ROWS_AT_ONCE = 512
EXPERTS_AT_ONCE = 8
VOCAB_AT_ONCE = 8192

# Changes to the mathematics that ``_forward`` can make on request
# (``depart=``). The tests show that each reads above ``TOLERANCE`` at the
# tiny size in float32, PERF.md what each reads at the published widths.
DEPARTURES = (
    "no_window",           # sliding layers see every earlier position
    "no_yarn",             # full layers: theta^(-i/64) as is, rotation x 1
    "gates_as_they_are",   # the 8 probabilities not divided by their sum
    "chip_0_experts",      # only experts 0-15 summed: the exchange left out
    "yarn_in_window",      # the sliding layers under YaRN too
)
# Departures the check on the chip cannot tell from the program's own
# rounding: none at the file's gain (above).
UNSEEN_ON_THE_CHIP = ()


def _kinds(sizes: dict):
    """Whether each layer is a sliding one, from the file's list."""
    kinds = sizes["layer_types"]
    if not (len(kinds) == len(sizes["mlp_layer_types"])
            == sizes["num_hidden_layers"]):
        raise ValueError("the per-layer lists do not name every layer")
    names = {"full_attention": False, "sliding_attention": True}
    return [names[k] for k in kinds]


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


def _axis(sizes: dict, mesh_spec):
    """The mesh axis the experts and the vocabulary lie along: the
    deployment's, where the mesh has more than one chip along it."""
    axis = sizes["deployment"]["axis"]
    return axis if getattr(mesh_spec, axis) > 1 else None


def model_config(sizes: dict, mesh_spec=None):
    full_rope = sizes["rope_parameters"]["full_attention"]
    slide_rope = sizes["rope_parameters"]["sliding_attention"]
    if (sizes["model_type"] != "mellum" or sizes["attention_bias"]
            or sizes["tie_word_embeddings"] or not sizes["norm_topk_prob"]
            or sizes["hidden_act"] != "silu"
            or slide_rope["rope_type"] != "default"
            or set(sizes["mlp_layer_types"]) != {"sparse"}):
        raise ValueError("not the block this builder writes down")
    axis = _axis(sizes, mesh_spec) if mesh_spec is not None else None
    return mellum2_12b_a2_5b(
        vocab_size=sizes["vocab_size"],
        d_model=sizes["hidden_size"],
        n_heads=sizes["num_attention_heads"],
        n_kv_heads=sizes["num_key_value_heads"],
        head_size=sizes["head_dim"],
        n_layers=sizes["num_hidden_layers"],
        layer_types=tuple(
            "window" if sliding else "attention" for sliding in _kinds(sizes)
        ),
        d_ff=sizes["intermediate_size"],
        max_len=sizes["max_position_embeddings"],
        norm_eps=sizes["rms_norm_eps"],
        rope_theta=float(full_rope["rope_theta"]),
        rope_yarn=_yarn(full_rope),
        window=WindowConfig(
            window=sizes["sliding_window"],
            n_heads=sizes["num_attention_heads"],
            rope_theta=float(slide_rope["rope_theta"]),
        ),
        n_experts=sizes["num_experts"],
        top_k=sizes["num_experts_per_tok"],
        d_expert=sizes["moe_intermediate_size"],
        embed_init_std=sizes["init"]["embedding_std"],
        attention_impl=sizes["attention_impl"],
        remat=sizes.get("remat", False),
        dtype=jnp.dtype(sizes["compute_dtype"]),
        param_dtype=jnp.dtype(sizes["param_dtype"]),
        mesh=mesh_spec.build() if axis else None,
        state_axis=axis,
    )


def balanced_placement(load, chips: int):
    """Which experts each of ``chips`` chips holds, from the pairs each
    expert received (``load`` [E]): the experts heaviest first, each onto
    the chip whose experts so far received least and which still has room
    for one (``E / chips`` a chip), as an expert-parallel load balancer
    places them from observed loads. Returns the experts ordered by chip,
    chip 0's first, each chip's by index: ``order`` [E]. Traceable."""
    experts = load.shape[0]
    room = experts // chips
    heaviest_first = jnp.argsort(-load, stable=True)

    def place(i, carry):
        total, held, chip_of = carry
        expert = heaviest_first[i]
        chip = jnp.argmin(jnp.where(held < room, total, jnp.inf))
        return (total.at[chip].add(load[expert]), held.at[chip].add(1),
                chip_of.at[expert].set(chip))

    _, _, chip_of = jax.lax.fori_loop(0, experts, place, (
        jnp.zeros(chips, jnp.float32), jnp.zeros(chips, jnp.int32),
        jnp.zeros(experts, jnp.int32),
    ))
    return jnp.argsort(chip_of, stable=True)


def place_experts(variables, sown, chips: int):
    """``variables`` as ``model.init`` returns them with every routed
    layer's experts RENUMBERED in the order :func:`balanced_placement`
    gives from the pairs that layer's experts received in one pass
    (``sown``: a step's statistics, its ``expert_tokens``): the router's
    columns and the three stacked weights take the same order, so the
    layer computes what it computed and expert ``j`` of the new numbering
    lies on chip ``j // (E / chips)``. The model is the one ``init`` drew;
    which chip holds which expert is the deployment's to say."""
    from flax.core import meta
    from flax.traverse_util import flatten_dict, unflatten_dict

    params = flatten_dict(dict(variables["params"]))

    def renumber(path, take):
        params[path] = meta.replace_boxed(
            params[path], take(meta.unbox(params[path]))
        )

    for path, load in flatten_dict(dict(sown)).items():
        if path[-1] != "expert_tokens":
            continue
        order = balanced_placement(load, chips)
        renumber(path[:-1] + ("router", "kernel"), lambda w: w[:, order])
        for name in ("w_gate", "w_up", "w_down"):
            renumber(path[:-1] + (name,), lambda w: w[order])
    return {**variables, "params": unflatten_dict(params)}


def scale_expert_stacks(variables, gain: float):
    """``variables`` as ``model.init`` returns them with every routed
    layer's three stacked expert matrices times ``gain``. The library
    draws a ``[E, D, F]`` stack xavier-uniform over the WHOLE stack, so at
    E = 64 each expert's matrix is 1/8 of what a matrix of its own would
    be (a gain of 8 is the per-expert fan) and a layer's routed sum, three
    such matrices deep, about 1/500 of the residual stream: the experts of
    a fresh model then do not reach the logits, and nothing that compares
    logits can see what happens inside a routed layer."""
    from flax.core import meta
    from flax.traverse_util import flatten_dict, unflatten_dict

    params = flatten_dict(dict(variables["params"]))
    for path, leaf in params.items():
        if path[-2] == "moe" and path[-1] in ("w_gate", "w_up", "w_down"):
            params[path] = meta.replace_boxed(leaf, meta.unbox(leaf) * gain)
    return {**variables, "params": unflatten_dict(params)}


def deployed_group(cfg, gain: float = 1.0, place: bool = False) -> CausalLM:
    """``CausalLM(cfg)`` as the configuration's ``init`` and ``deployment``
    groups say, where the weights are drawn and nowhere in the step:
    ``init`` draws the weights as the model does, multiplies the expert
    stacks by ``gain`` (:func:`scale_expert_stacks`) and, with ``place``,
    runs ONE forward pass of them over the sequence it was given (once a
    chip of the experts' axis, so that the batch divides over it) and
    places each layer's experts on the chips by the loads of that pass
    (:func:`place_experts`). The whole forward pass, not the first router
    alone: a later layer's loads depend on what the earlier layers'
    experts wrote into the residual stream. Why a placement: a step waits
    for the chip whose experts received most, a fresh router sends a
    Zipf-distributed corpus's few frequent words to a few experts, and
    which chip holds them differs by seed (PERF.md section 6, PR 53: five
    seeds 5% apart without it, and (chip, layer-step)s over one and a half
    times the uniform share in two of them). (Both are closed over and no
    fields: a module the harness loads by path cannot declare one.)"""
    from raydp_tpu.models import stats

    chips = cfg.chips_along(cfg.state_axis)

    class DeployedGroup(CausalLM):
        def init(self, rngs, ids, **kwargs):
            variables = scale_expert_stacks(
                super().init(rngs, ids, **kwargs), gain
            )
            if not place:
                return variables
            _, sown = self.apply(
                {"params": variables["params"]},
                jnp.tile(ids, (chips,) + (1,) * (ids.ndim - 1)),
                mutable=[stats.STATS, "losses"],
            )
            return place_experts(variables, sown[stats.STATS], chips)

    return DeployedGroup(cfg)


def deployed_model(sizes: dict, mesh_spec=None) -> CausalLM:
    """The model as the configuration's ``init`` and ``deployment`` groups
    say: the expert stacks at ``init.expert_stack_gain`` where the file
    gives one, over the chips of the deployment's axis, the experts placed
    by load where the group has more than one chip and the file names a
    placement."""
    cfg = model_config(sizes, mesh_spec)
    gain = float(sizes["init"].get("expert_stack_gain", 1.0))
    place = cfg.chips_along(cfg.state_axis) > 1 and bool(
        sizes["deployment"].get("placement"))
    if gain == 1.0 and not place:
        return CausalLM(cfg)
    return deployed_group(cfg, gain, place)


def _optimizer(opt: dict):
    """``optax.<name>`` at the configuration's rate, reached by a linear
    warm-up from 0 over ``warmup_steps`` steps where the file gives them."""
    import optax

    rate = opt["learning_rate"]
    if opt.get("warmup_steps"):
        rate = optax.linear_schedule(0.0, rate, opt["warmup_steps"])
    return getattr(optax, opt["name"])(rate)


def estimator_kwargs(sizes: dict, traffic: dict, mesh_spec) -> dict:
    """Arguments of ``JAXEstimator`` for this configuration on
    ``mesh_spec``: the model told the axis its experts and its vocabulary
    lie along (the estimator lays the two tables and their moments there
    at rest from the model's own configuration). ``aux_losses`` is on for
    the routing counts the step sows (both loss weights are 0: the
    configuration has no auxiliary loss)."""
    return dict(
        model=deployed_model(sizes, mesh_spec),
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


def _inv_freq(dim: int, rope: dict) -> np.ndarray:
    """The ``dim / 2`` rotary frequencies of one kind of layer:
    ``theta^(-2i/dim)``, and under ``rope_type`` yarn (``transformers``'
    ``_compute_yarn_parameters`` transcribed) that where feature pair i
    turns more than ``beta_fast`` times over the original context, that
    over ``factor`` where fewer than ``beta_slow`` times, a linear ramp
    between."""
    base = float(rope["rope_theta"])
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rope["rope_type"] == "default":
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


def _positions(x, sizes: dict, sliding: bool, depart):
    """``x`` [S, H, 128] with the layer kind's positions turned in: all
    128 features, feature i paired with i + 64 (the published
    ``rotate_half``); under YaRN cos and sin times ``attention_factor``."""
    ropes = sizes["rope_parameters"]
    rope = ropes["sliding_attention" if sliding else "full_attention"]
    if depart == "no_yarn" and not sliding:
        rope = dict(rope, rope_type="default")
    if depart == "yarn_in_window" and sliding:
        rope = ropes["full_attention"]
    factor = float(rope["attention_factor"]) if (
        rope["rope_type"] == "yarn") else 1.0
    half = x.shape[-1] // 2
    angle = np.arange(x.shape[0], dtype=np.float32)[:, None] * _inv_freq(
        x.shape[-1], rope
    )
    cos = jnp.asarray(np.cos(angle) * factor)[:, None]
    sin = jnp.asarray(np.sin(angle) * factor)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(p, y, sizes: dict, sliding: bool, r, depart):
    """One sequence ``y`` [S, D]. Dense softmax attention, a key-value
    head and ``QUERY_ROWS_AT_ONCE`` query rows at a time (``lax.map``);
    query head h reads key-value head ``h // 8``. A sliding layer's rows
    see the last ``sliding_window`` keys, their own among them."""
    s, head = y.shape[0], sizes["head_dim"]
    kv_heads = sizes["num_key_value_heads"]
    heads = sizes["num_attention_heads"]
    group = heads // kv_heads
    q = jnp.einsum("sd,dhk->shk", r(y), r(p["q"]["kernel"]))
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
    return jnp.einsum("shk,hkd->sd", r(ctx), r(p["out"]["kernel"]))


def _routed(p, y, sizes: dict, r, depart):
    """``sum_j g_j E_j(y)`` over ALL the experts: every token through each
    of them, times a mask of the router's choice (no sort, no grouped
    matmul, no kernel, no mesh). Softmax over the 64 scores in float32, the
    8 largest, their probabilities divided by their sum."""
    n_exp, top_k = sizes["num_experts"], sizes["num_experts_per_tok"]
    probs = jax.nn.softmax(y @ p["router"]["kernel"], axis=-1)
    # The k largest; equal values go to the lower index.
    by_size = jnp.argsort(-probs, axis=-1, stable=True)
    mask = jnp.argsort(by_size, axis=-1) < top_k
    weights = jnp.where(mask, probs, 0.0)
    if depart != "gates_as_they_are":
        weights = weights / weights.sum(axis=-1, keepdims=True)
    if depart == "chip_0_experts":
        n_exp = n_exp // sizes["deployment"]["chips_sharing_a_layer"]
    at_once = min(EXPERTS_AT_ONCE, n_exp)
    if n_exp % at_once:
        raise ValueError(f"{n_exp} experts in blocks of {at_once}")

    def blocks(w):
        """The first ``n_exp`` experts' ``[E, ...]`` as blocks of them."""
        return w[:n_exp].reshape((n_exp // at_once, at_once) + w.shape[1:])

    def one_block(block):
        """[T, D] from ``at_once`` experts (``lax.map``: one body to
        compile, whatever the number of experts)."""
        w_gate, w_up, w_down, gates = block
        h = jax.nn.silu(
            jnp.einsum("td,edf->tef", r(y), r(w_gate))
        ) * jnp.einsum("td,edf->tef", r(y), r(w_up))
        part = jnp.einsum("tef,efd->ted", r(h), r(w_down))
        return jnp.einsum("ted,et->td", part, gates)

    return jax.lax.map(one_block, (
        blocks(p["w_gate"]), blocks(p["w_up"]), blocks(p["w_down"]),
        blocks(weights.T),
    )).sum(axis=0)


def _forward(params, ids, sizes: dict, trunk=None, depart=None):
    """Logits of the stack as ISSUE 53 writes it down (attention and
    rotary scaling as ``transformers`` computes them for these config keys,
    routing in the softmax-and-normalise form; written from the config
    and the papers: no network), straightforward float32 ``jax.numpy`` on
    the program's parameter tree, one sequence at a time:

        x = E[ids]
        per layer:  y = rms(x);  x += W_o attend(y)
                    z = rms(x);  x += sum of the token's 8 experts
        logits = rms(x) W_head

    ``trunk`` is None for the reference; a dtype rounds the blocks'
    weights and every matmul's inputs to it (router, gates, norms and the
    head stay float32, as the configuration states), which shows what the
    tolerance refuses. ``depart`` names one of ``DEPARTURES``."""
    if depart is not None and depart not in DEPARTURES:
        raise ValueError(f"unknown departure {depart!r}")
    enc = params["params"]["encoder"]
    eps = sizes["rms_norm_eps"]
    if trunk is None:
        r = lambda a: a  # noqa: E731
    else:
        r = lambda a: a.astype(trunk).astype(jnp.float32)  # noqa: E731
    head = params["params"]["lm_head"]["kernel"]              # [D, V]

    def one_sequence(row):
        x = r(enc["tok_embed"]["embedding"][row])             # [S, D]
        for i, sliding in enumerate(_kinds(sizes)):
            blk = enc[f"block_{i}"]
            attn = blk["attn_window" if sliding else "attn"]
            y = _rms_norm(x, blk["ln_attn"]["scale"], eps)
            x = x + _attention(attn, y, sizes, sliding, r, depart)
            z = _rms_norm(x, blk["ln_mlp"]["scale"], eps)
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
    """Next-token cross-entropy over the whole vocabulary (the
    configuration has no auxiliary loss) and its gradients with respect to
    ``params`` (the CPU tests compare the program's against them)."""
    def loss(p):
        logp = jax.nn.log_softmax(_forward(p, ids, sizes)[:, :-1], axis=-1)
        return -jnp.mean(
            jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)
        )

    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss)(params)


# ------------------------------------------------ operation and byte counts
#
# ``flops_per_sample`` is one sequence's; ``model.mfu`` multiplies it by the
# host's sequences a second and divides by four chips' peak,
# ``train_step_roofline`` takes ``per_chip_batch`` sequences' operations
# and ONE chip's bytes against one chip's peaks: both count the FLOPs of
# all four chips against four chips' peak. The kernels' counts take the
# chip's ``batch`` and are read against chip 0's kernels; the grouped
# matmuls' take the rows that reached chip 0's own experts, whatever chip
# their tokens came from.

def _layers_of(sizes: dict, sliding: bool) -> int:
    return sum(1 for kind in _kinds(sizes) if kind == sliding)


def _chips(sizes: dict) -> int:
    return sizes["deployment"]["chips_here"]


def _matrix_params(sizes: dict) -> dict:
    """Matrix parameters a token touches, by where: one attention layer
    (q, k and v, the output), a router, ONE expert, the head."""
    d, head = sizes["hidden_size"], sizes["head_dim"]
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    return {
        "attention": d * head * (2 * heads + 2 * kv),
        "router": d * sizes["num_experts"],
        "expert": 3 * d * sizes["moe_intermediate_size"],
        "head": d * sizes["vocab_size"],
    }


def n_params(sizes: dict) -> int:
    """Trained parameters of the layers built, over all the chips."""
    m, d = _matrix_params(sizes), sizes["hidden_size"]
    return sizes["num_hidden_layers"] * (
        m["attention"] + m["router"] + sizes["num_experts"] * m["expert"]
        + 2 * d
    ) + 2 * m["head"] + d


def n_params_a_chip(sizes: dict) -> int:
    """Trained parameters ONE chip holds at rest: attention, routers and
    norms whole, its share of the experts and of the two vocabulary
    tables."""
    m, d = _matrix_params(sizes), sizes["hidden_size"]
    chips = _chips(sizes)
    return sizes["num_hidden_layers"] * (
        m["attention"] + m["router"]
        + sizes["num_experts"] // chips * m["expert"] + 2 * d
    ) + 2 * m["head"] // chips + d


def _pairs_per_step(sizes: dict, traffic: dict, batch: int) -> float:
    """(token, expert) pairs of one step that reached CHIP 0's experts,
    over all routed layers: what the program counted on the device over
    its last epoch for the first chip of the experts' axis, the mesh's
    first device and the one whose kernels the device trace shows (gauge
    ``moe/first_chip_pairs_per_step``: with the heaviest expert placed
    there first, chip 0 is not the mean chip). Before the first epoch, or
    from a program without that gauge, a chip's share of the count at any
    routing: ``T · k`` a layer over the host's tokens over the chips."""
    from raydp_tpu.utils.profiling import metrics

    counted = metrics.gauge_value("moe/first_chip_pairs_per_step")
    if counted:
        return float(counted)
    return (sizes["num_hidden_layers"] * batch * traffic["seq_len"]
            * sizes["num_experts_per_tok"])


def moe_flops_per_step(sizes: dict, traffic: dict, batch: int) -> float:
    """Operations of CHIP 0's grouped matmuls in one step, forward and
    backward: the pairs that reached its experts (``_pairs_per_step``:
    the traced chip's own, whatever chip their tokens came from), three
    ``[D, F]`` matrices a row, 2 operations a multiply-add, 3 passes
    (forward, input gradient, weight gradient)."""
    per_row = 2 * _matrix_params(sizes)["expert"]
    return 3.0 * _pairs_per_step(sizes, traffic, batch) * per_row


def _pairs(sizes: dict, s: int, sliding: bool) -> float:
    """(query, key) pairs of one head that exist: ``S(S+1)/2`` over all
    earlier positions, ``S W - W(W-1)/2`` inside a window of W."""
    w = min(sizes["sliding_window"], s) if sliding else s
    return s * w - w * (w - 1) / 2


def _kernel_flops(sizes: dict, traffic: dict, batch: int,
                  sliding: bool) -> float:
    """Operations of one kind of layer's attention kernels in one chip's
    step (``batch`` sequences): the pairs that exist, 2 operations a
    multiply-add, two products of ``head_dim`` forward and five backward
    (3.5 x forward). Nothing recomputed is counted."""
    pairs = sizes["num_attention_heads"] * _pairs(
        sizes, traffic["seq_len"], sliding
    )
    return _layers_of(sizes, sliding) * batch * pairs * 2.0 * (
        7 * sizes["head_dim"]
    )


def attention_flops_per_step(sizes: dict, traffic: dict, batch: int) -> float:
    """The FULL layers' kernels (the Pallas calls under ``attn``)."""
    return _kernel_flops(sizes, traffic, batch, False)


def window_attention_flops_per_step(sizes: dict, traffic: dict,
                                    batch: int) -> float:
    """The sliding layers' kernels (the Pallas calls under
    ``attn_window``), over the pairs inside the window only."""
    return _kernel_flops(sizes, traffic, batch, True)


def flops_per_sample(sizes: dict, traffic: dict) -> float:
    """Operations the forward and backward passes need for one sequence:
    3 x (2 x matrix parameters a token touches x tokens + attention's
    scores and mixing over the pairs that exist, inside the window only
    for a sliding layer). A token touches its layer's attention
    projections, a router and 8 experts, and the head. The embedding
    lookup is a gather; norms and gates are not matmuls; nothing
    recomputed and nothing the exchange moves is counted."""
    s = traffic["seq_len"]
    m = _matrix_params(sizes)
    per_token = sizes["num_hidden_layers"] * (
        m["attention"] + m["router"]
        + sizes["num_experts_per_tok"] * m["expert"]
    ) + m["head"]
    attention = 2 * 2 * sizes["head_dim"] * sizes["num_attention_heads"] * (
        _layers_of(sizes, False) * _pairs(sizes, s, False)
        + _layers_of(sizes, True) * _pairs(sizes, s, True)
    )
    return 3.0 * (2 * per_token * s + attention)


def bytes_per_step(sizes: dict, traffic: dict, batch: int) -> float:
    """Bytes ONE chip's step has to move through its memory whatever the
    schedule: every parameter it holds, its gradient and both AdamW
    moments read and written once in float32, and the batch read.
    Activations and what the exchange brings are left out, so this is a
    lower bound."""
    return 8.0 * 4 * n_params_a_chip(sizes) + 4.0 * batch * traffic["seq_len"]


def exchange_bytes_per_step(sizes: dict, traffic: dict, batch: int) -> float:
    """Bytes ONE chip sends plus receives in the routed layers' exchange
    in one step, from the shapes (``models/moe.exchange_bytes`` written
    out again, so that the benchmark's count does not move with the
    program's): a layer's gather brings the other chips' ``(n-1) · batch ·
    S`` tokens in — ``D`` features in the compute dtype, 8 gates and 8
    choices of 4 bytes — and sends this chip's ``batch · S`` to ``n-1``
    chips, its reduce-scatter moves as many rows of ``D`` features the
    other way; a backward pass moves the same, and checkpointed blocks run
    the forward twice."""
    n = _chips(sizes)
    rows = 2 * (n - 1) * batch * traffic["seq_len"]
    row = sizes["hidden_size"] * jnp.dtype(sizes["compute_dtype"]).itemsize
    passes = 3 if sizes.get("remat", False) else 2
    return float(sizes["num_hidden_layers"] * passes * rows * (
        2 * row + 8 * sizes["num_experts_per_tok"]
    ))
