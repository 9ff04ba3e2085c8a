"""The Olmo-Hybrid style stack on the normal path, at tiny widths on the
CPU (hidden 64; Gated DeltaNet with 2 heads, keys of 12 and values of 24,
4-tap convolutions, beta in (0, 2) in layers 1-3, full attention with 4
heads of 16 and a norm over the whole q and k projections in layer 4; a
norm on each sublayer's OUTPUT and none on its input; sequence 80, chunks
of 16, vocabulary 512), float32: the program against the benchmark's
plain reference, whose delta rule runs token by token (logits, loss, every
gradient), every departure the builder lists against its tolerance, the
mixer against the reference's ``_gdn``, where the norms sit for each value
of ``branch_norm``, the scopes, gauges and the one log line a built step
leaves, and the block checkpoint's rule at this state."""
import importlib.util
import logging
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raydp_tpu.models import CausalLM, GDNConfig, olmo_hybrid_7b
from raydp_tpu.models import gdn as gdn_module
from raydp_tpu.models import step as model_step
from raydp_tpu.models.gdn import GatedDeltaMixer
from raydp_tpu.models.kda import HeadGatedRMSNorm
from raydp_tpu.models.transformer import MIXERS, kept_names
from raydp_tpu.ops import kda as kda_ops
from raydp_tpu.train.losses import lm_crossentropy
from raydp_tpu.utils.profiling import metrics
from tests.test_gdn import _run_once

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ = 80                    # five chunks of gcd(64, 80) = 16
PERIOD = ["linear_attention"] * 3 + ["full_attention"]
SIZES = {
    "model_type": "olmo_hybrid", "vocab_size": 512, "hidden_size": 64,
    "intermediate_size": 96, "num_hidden_layers": 4,
    "num_attention_heads": 4, "num_key_value_heads": 4, "hidden_act": "silu",
    "max_position_embeddings": 256, "attention_bias": False,
    "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
    "layer_types": PERIOD, "linear_num_key_heads": 2,
    "linear_num_value_heads": 2, "linear_key_head_dim": 12,
    "linear_value_head_dim": 24, "linear_conv_kernel_dim": 4,
    "linear_allow_neg_eigval": True, "rope_parameters": {"rope_theta": None},
    "gdn": {"chunk": 64}, "attention_impl": "dense", "remat": True,
    "compute_dtype": "float32", "param_dtype": "float32",
    "init": {"embedding_std": 1.0},
}


@pytest.fixture(scope="module")
def builder():
    """The benchmark's builder file: the plain reference lives there."""
    path = os.path.join(REPO, "benchmark", "configs", "olmo_hybrid_lm.py")
    spec = importlib.util.spec_from_file_location("olmo_builder", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def tiny(builder):
    """Model, seeded weights with decays strong enough to matter inside a
    chunk (``dt_bias`` + 4 over its published draw) and betas pushed
    toward 2, ids; the walk over segments cut to two chunks a segment."""
    model = CausalLM(builder.model_config(SIZES))
    ids = jnp.asarray(np.random.default_rng(0).integers(
        0, SIZES["vocab_size"], (2, SEQ)).astype(np.int32))
    variables = _run_once(
        lambda: nn.unbox(model.init(jax.random.PRNGKey(0), ids)))
    enc = variables["params"]["encoder"]
    for i in range(3):
        decay = enc[f"block_{i}"]["gdn"]["decay"]
        decay["dt_bias"] = decay["dt_bias"] + 4.0
    return model, variables, ids


def _rel(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


@pytest.fixture(scope="module")
def logits(builder, tiny):
    """(program, reference) logits of the seeded model."""
    model, variables, ids = tiny
    got = _run_once(lambda v: model.apply(v, ids), variables)
    return got, _run_once(
        lambda v: builder.reference_logits(v, ids, SIZES), variables)


# ---------------------------------------------- program against reference

def test_parameter_tree_has_output_norms_and_no_input_norm(tiny):
    _, variables, _ = tiny
    enc = variables["params"]["encoder"]
    shapes = jax.tree_util.tree_map(lambda a: a.shape, enc)
    gdn = {
        "q_proj": {"kernel": (64, 24)}, "k_proj": {"kernel": (64, 24)},
        "v_proj": {"kernel": (64, 48)}, "g_proj": {"kernel": (64, 48)},
        "out": {"kernel": (48, 64)}, "beta": {"kernel": (64, 2)},
        "conv": {"q": {"kernel": (4, 24)}, "k": {"kernel": (4, 24)},
                 "v": {"kernel": (4, 48)}},
        "decay": {"proj": {"kernel": (64, 2)}, "A_log": (2,),
                  "dt_bias": (2,)},
        "gate_norm": {"scale": (24,)},
    }
    ffn = {"mlp_in": {"kernel": (64, 192)}, "mlp_out": {"kernel": (96, 64)},
           "ln_mlp_out": {"scale": (64,)}}
    for i in range(3):
        assert shapes[f"block_{i}"] == {
            "gdn": gdn, "ln_gdn_out": {"scale": (64,)}, **ffn}
    assert shapes["block_3"] == {
        "attn": {"qkv": {"kernel": (64, 3, 4, 16)},
                 "out": {"kernel": (4, 16, 64)},
                 "q_norm": {"scale": (64,)}, "k_norm": {"scale": (64,)}},
        "ln_attn_out": {"scale": (64,)}, **ffn}
    assert set(shapes) == {
        "tok_embed", "ln_final", "block_0", "block_1", "block_2", "block_3"}
    assert variables["params"]["lm_head"]["kernel"].shape == (64, 512)


def test_n_params_counts_the_tree(builder, tiny):
    _, variables, _ = tiny
    held = sum(int(np.prod(leaf.shape)) for leaf in
               jax.tree_util.tree_leaves(variables["params"]))
    assert builder.n_params(SIZES) == held


def test_the_seeded_decays_and_betas_are_strong(tiny):
    _, variables, ids = tiny
    p = variables["params"]["encoder"]["block_0"]["gdn"]
    x = variables["params"]["encoder"]["tok_embed"]["embedding"][ids[0]]
    g = -jnp.exp(p["decay"]["A_log"]) * jax.nn.softplus(
        x @ p["decay"]["proj"]["kernel"] + p["decay"]["dt_bias"])
    beta = 2 * jax.nn.sigmoid(x @ p["beta"]["kernel"])
    assert float(g.reshape(5, 16, 2).sum(1).min()) < -30
    assert float(beta.max()) > 1.5 and float(beta.min()) < 0.5


def test_logits_match_the_plain_reference(logits):
    got, want = logits
    assert got.shape == (2, SEQ, SIZES["vocab_size"])
    assert _rel(got, want) < 5e-5


def test_loss_and_gradients_match_the_plain_reference(builder, tiny,
                                                      monkeypatch):
    monkeypatch.setattr(kda_ops, "SEGMENT_CHUNKS", 2)    # three segments
    model, variables, ids = tiny

    def loss(params):
        return lm_crossentropy(model.apply({"params": params}, ids), ids)

    got, grads = _run_once(jax.value_and_grad(loss), variables["params"])
    want, want_grads = _run_once(
        lambda v: builder.reference_loss_and_grads(v, ids, SIZES), variables)
    assert abs(float(got) - float(want)) < 1e-5
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    want_flat = dict(jax.tree_util.tree_flatten_with_path(
        want_grads["params"])[0])
    # 3 x (13 mixer + 1 norm) + (4 attention + 1 norm) + 4 x 3 FFN + 3.
    assert len(flat) == len(want_flat) == 62
    for path, g in flat:
        w = want_flat[path]
        assert float(jnp.max(jnp.abs(g - w))) <= 5e-4 * float(
            jnp.max(jnp.abs(w))) + 1e-8, jax.tree_util.keystr(path)


def test_the_departures_are_the_builders_list(builder):
    assert builder.DEPARTURES == (
        "beta_not_doubled", "no_decay", "norm_on_input", "state_bfloat16")
    assert set(builder.UNSEEN_ON_THE_CHIP) <= set(builder.DEPARTURES)
    with pytest.raises(ValueError, match="unknown departure"):
        builder.reference_logits({}, None, SIZES, depart="no_such_thing")


@pytest.mark.parametrize("depart", [
    "beta_not_doubled", "no_decay", "norm_on_input", "state_bfloat16",
])
def test_tolerance_refuses_a_departure_from_the_mathematics(
        builder, tiny, logits, depart):
    """Each departure moves the float32 reference's logits by more than
    the cell's tolerance, where the program's own are 3e-5 from it. The
    state's products in bfloat16 cannot: they move the logits by less
    than a bf16 trunk does (1.46% against the program's 1.45% on the
    chip), under any tolerance bf16 leaves room for; the float32
    comparison here sees it a thousand times over its own error (and the
    chip's check lists it as unseen)."""
    _, variables, ids = tiny
    got, want = logits
    moved = _rel(_run_once(lambda v: builder.reference_logits(
        v, ids, SIZES, depart=depart), variables), want)
    assert builder.TOLERANCE > 500 * _rel(got, want)
    if depart == "state_bfloat16":
        assert moved > 500 * _rel(got, want)
        assert builder.UNSEEN_ON_THE_CHIP == (depart,)
    else:
        assert moved > builder.TOLERANCE


def test_a_bfloat16_trunk_is_seen_and_float8_is_outside(builder, tiny,
                                                        logits):
    _, variables, ids = tiny
    _, want = logits
    for trunk, outside in ((jnp.bfloat16, None), (jnp.float8_e4m3fn, True)):
        moved = _rel(_run_once(lambda v: builder.reference_logits(
            v, ids, SIZES, trunk=trunk), variables), want)
        if outside:
            assert moved > builder.TOLERANCE
        else:
            assert moved > 1e-4       # rounding is seen, whatever it reads


def test_the_reference_imports_nothing_of_the_programs_models_or_ops(builder):
    """The reference's functions reach ``jax``, ``numpy`` and this file's
    own helpers alone; the builder's two imports from the program are
    what ``model_config`` builds the program's model with."""
    with open(builder.__file__) as f:
        source = f.read()
    imports = [line for line in source.splitlines()
               if line.startswith(("import ", "from "))]
    assert [line for line in imports if "raydp_tpu" in line] == [
        "from raydp_tpu.models.gdn import GDNConfig",
        "from raydp_tpu.models.transformer import CausalLM, olmo_hybrid_7b",
    ]
    reference = source[source.index("# ---------------------------------"
                                    "--------------------- plain reference"):
                       source.index("def reference_loss_and_grads")]
    for name in ("GDNConfig", "CausalLM", "olmo_hybrid_7b", "raydp_tpu"):
        assert name not in reference
    assert 'default_matmul_precision("highest")' in source


# ------------------------------------------------------ the mixer's parts

@pytest.mark.parametrize("seq", [80, 64, 33])
def test_the_mixer_is_the_references_gdn(builder, seq):
    """``GatedDeltaMixer`` alone against the reference's ``_gdn`` on one
    sequence; 33 tokens run one token a chunk."""
    cfg = builder.model_config(SIZES)
    rng = np.random.default_rng(seq)
    x = jnp.asarray(rng.standard_normal((1, seq, 64)), jnp.float32)
    mixer = GatedDeltaMixer(cfg)
    variables = nn.unbox(jax.jit(mixer.init)(jax.random.PRNGKey(2), x))
    p = variables["params"]
    p["decay"]["dt_bias"] = p["decay"]["dt_bias"] + 2.0
    # Each side one compiled program: op by op they take twice as long.
    got = jax.jit(mixer.apply)(variables, x)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(
            lambda p, x: builder._gdn(p, x, SIZES, lambda a: a, None))(p, x[0])
    assert cfg.gdn.scan_chunk(seq) == {80: 16, 64: 64, 33: 1}[seq]
    assert _rel(got[0], want) < 2e-5


def test_the_gated_norm_takes_its_activation_as_an_argument():
    """One module for Kimi Linear's sigmoid gate and this layer's SiLU:
    ``rms(o_h) w act(z_h)``, the sigmoid where none is given."""
    rng = np.random.default_rng(0)
    o = jnp.asarray(rng.standard_normal((1, 5, 2, 8)), jnp.float32)
    z = jnp.asarray(rng.standard_normal((1, 5, 16)), jnp.float32)
    normed = o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True) + 1e-6)
    for activation, fn in ((None, jax.nn.sigmoid), (jax.nn.silu, jax.nn.silu)):
        kwargs = {} if activation is None else {"activation": activation}
        norm = HeadGatedRMSNorm(1e-6, jnp.float32, jnp.float32, **kwargs)
        variables = norm.init(jax.random.PRNGKey(0), o, z)
        np.testing.assert_allclose(
            norm.apply(variables, o, z), normed * fn(z).reshape(o.shape),
            rtol=1e-6, atol=1e-6)


def test_the_mixer_is_one_of_the_stacks_kinds():
    assert "gdn" in MIXERS and "kda" in MIXERS
    cfg = olmo_hybrid_7b(n_layers=8)
    assert cfg.kinds == ("gdn", "gdn", "gdn", "attention") * 2
    assert gdn_module.layers_of(cfg) == 6
    assert not cfg.serves_from_kv_cache
    assert {"gdn_out", "gdn_segment_states", "kda_out"} <= set(kept_names())
    model = CausalLM(olmo_hybrid_7b(
        vocab_size=64, d_model=32, n_heads=4, n_layers=1, d_ff=48,
        max_len=64, gdn=GDNConfig(heads=2, key_dim=8, value_dim=8, chunk=8)))
    with pytest.raises(NotImplementedError, match="cache of its own"):
        model.init_cache(1)


# ------------------------------------------------- where the norms sit

def _norms(branch_norm, **more):
    cfg = olmo_hybrid_7b(**{**dict(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=48,
        max_len=64, branch_norm=branch_norm,
        gdn=GDNConfig(heads=2, key_dim=8, value_dim=8, chunk=8),
        layer_types=("gdn:swiglu", "attention:swiglu")), **more})
    tree = jax.eval_shape(lambda: nn.unbox(CausalLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32))))
    return {block: {n for n in sub if n.startswith("ln_")}
            for block, sub in tree["params"]["encoder"].items()
            if block.startswith("block_")}


@pytest.mark.parametrize("branch_norm,want", [
    (False, [{"ln_gdn", "ln_mlp"}, {"ln_attn", "ln_mlp"}]),
    (True, [{"ln_gdn", "ln_gdn_out", "ln_mlp", "ln_mlp_out"},
            {"ln_attn", "ln_attn_out", "ln_mlp", "ln_mlp_out"}]),
    ("only", [{"ln_gdn_out", "ln_mlp_out"}, {"ln_attn_out", "ln_mlp_out"}]),
])
def test_the_norm_placement_is_one_statement(branch_norm, want):
    """``branch_norm`` False | True | "only": input alone, both, output
    alone; a norm that does not sit is not in the tree."""
    assert list(_norms(branch_norm).values()) == want


def test_an_unknown_placement_is_refused():
    with pytest.raises(ValueError, match="branch_norm"):
        _norms("output")


@pytest.mark.parametrize("more,want", [
    (dict(layer_types=("attention:none", "none:swiglu")),
     [{"ln_attn_out"}, {"ln_mlp_out"}]),
    (dict(layer_types=("conv", "mamba"), ssm_heads=4, ssm_head_dim=8,
          ssm_state=8, ssm_chunk=8),
     [{"ln_conv_out", "ln_mlp_out"}, {"ln_mamba_out", "ln_mlp_out"}]),
], ids=["one_sublayer", "conv_mamba"])
def test_outputs_only_holds_for_every_kind_of_layer(more, want):
    assert list(_norms("only", **more).values()) == want


def test_outputs_only_is_x_plus_norm_of_f_of_x(tiny):
    """The first layer by hand: ``h = x + rms(Mixer(x))``, the mixer
    reading the embedding itself."""
    model, variables, ids = tiny
    enc = variables["params"]["encoder"]
    x = enc["tok_embed"]["embedding"][ids]
    mixer = GatedDeltaMixer(model.cfg)
    branch = jax.jit(mixer.apply)({"params": enc["block_0"]["gdn"]}, x)
    normed = branch / jnp.sqrt(
        jnp.mean(branch * branch, -1, keepdims=True) + 1e-6)
    want = x + normed * enc["block_0"]["ln_gdn_out"]["scale"]
    _, state = jax.jit(lambda v: model.apply(
        v, ids, capture_intermediates=lambda m, _: m.name in (
            "ln_gdn_out",), mutable=["intermediates"]))(variables)
    got = state["intermediates"]["encoder"]["block_0"]["ln_gdn_out"][
        "__call__"][0]
    np.testing.assert_allclose(x + got, want, rtol=1e-5, atol=1e-5)


# --------------------------------------------- spans, gauges, the log line

SCOPES = ("q_proj", "k_proj", "v_proj", "conv", "decay", "beta", "scan",
          "g_proj", "gate_norm", "out")


@pytest.fixture(scope="module")
def lowered(tiny):
    model, variables, ids = tiny
    return jax.jit(lambda v: model.apply(v, ids)).lower(
        variables).as_text(debug_info=True)


@pytest.mark.parametrize("scope", SCOPES)
def test_the_mixer_names_its_scopes(lowered, scope):
    """What the benchmark's part rules read: ``block_i/gdn/<scope>`` in
    the lowered program's locations."""
    assert f"block_0/gdn/{scope}" in lowered
    assert f"block_2/gdn/{scope}" in lowered


def test_the_output_norms_and_the_full_layer_keep_their_scopes(lowered):
    assert "block_0/ln_gdn_out" in lowered and "block_0/ln_mlp_out" in lowered
    assert "block_3/attn/q_norm" in lowered
    assert "block_3/ln_attn_out" in lowered
    assert "block_3/gdn" not in lowered and "block_0/ln_gdn/" not in lowered
    assert "ln_mlp/" not in lowered and "ln_attn/" not in lowered


@pytest.mark.parametrize("gauge,value", [
    ("gdn/layers", 24), ("gdn/heads", 30), ("gdn/chunk", 64),
    ("gdn/chunks_per_step", 24 * 64),
    ("gdn/state_bytes_per_sequence", 24 * 30 * 96 * 192 * 4),
    ("gdn/kept_bytes_per_sequence",
     24 * 30 * 192 * (2 * 4096 + 4 * 2 * 96)),
])
def test_the_report_of_the_published_stack(gauge, value, caplog):
    cfg = olmo_hybrid_7b()
    with caplog.at_level(logging.INFO, logger="raydp_tpu.models.gdn"):
        gdn_module.report(cfg, tokens_per_step=4096)
    assert metrics.gauge_value(gauge) == value
    (record,) = [r for r in caplog.records
                 if r.name == "raydp_tpu.models.gdn"]
    line = record.getMessage()
    assert "gdn gdn gdn attention" in line
    assert "30 heads of 96 (q, k) and 192 (v)" in line
    assert "beta in (0, 2)" in line and "chunk 64 (1536 chunks a step)" in line
    # No TPU here: the three convolutions take the jax.numpy form.
    assert "q: jax.numpy, k: jax.numpy, v: jax.numpy" in line


def test_the_report_reads_zero_for_the_other_stacks(caplog):
    from raydp_tpu.models import kimi_linear_48b_a3b, tiny_transformer

    for cfg in (kimi_linear_48b_a3b(n_layers=5), tiny_transformer(), None):
        with caplog.at_level(logging.INFO, logger="raydp_tpu.models.gdn"):
            gdn_module.report(cfg, tokens_per_step=4096)
        for gauge in ("gdn/layers", "gdn/heads", "gdn/chunk",
                      "gdn/chunks_per_step", "gdn/state_bytes_per_sequence",
                      "gdn/kept_bytes_per_sequence"):
            assert metrics.gauge_value(gauge) == 0
    assert not [r for r in caplog.records
                if r.name == "raydp_tpu.models.gdn"]


@pytest.mark.parametrize("channels,takes", [(2880, False), (5760, True)])
def test_which_convolutions_the_kernels_take_at_the_published_widths(
        channels, takes):
    """30 heads of 192 are 45 lane tiles, 30 heads of 96 are 22.5: the
    kernels take the first and decline the second (q and k run the
    ``jax.numpy`` form), on shapes alone."""
    from raydp_tpu.ops import causal_conv

    assert causal_conv.uses_kernel(
        4096, channels, 4, jnp.bfloat16, jnp.float32) is takes


# ------------------------------------------- the block checkpoint's rule

MIB = 2 ** 20
V5E = 16_909_336_064
# The published step's stack as ``fit_checkpoint`` counts it for a v5e
# (scripts/checkpoint_rows.py olmo_hybrid_7b.fit_stage, PR 63), in MiB.
STAGE = model_step.Stack(
    *([size * MIB for size in sizes] for sizes in (
        [538, 538, 538, 473], [79, 79, 79, 60], [1133, 1133, 1133, 830],
        [411, 411, 411, 354])),
    fixed=10630 * MIB, head=196 * MIB, head_stays=0,
    parameters=[size * MIB for size in (822, 822, 822, 709)])


def test_nothing_is_released_where_the_state_is_most_of_the_chip():
    """The estimate alone would release all four blocks (13.4 GiB of the
    14.96 it may use) where the compiled step reads 15.05: it has the
    gradients in the compute dtype, 1.5 GiB short where the step holds
    most, and its slack there is 0.45. The rule keeps every block
    checkpointed; with the gradients counted whole (or not known) it
    releases them."""
    everything = (0, 1, 2, 3)
    assert model_step.estimated_bytes(STAGE, everything).total < 0.95 * V5E
    short = model_step.uncounted_bytes(STAGE, everything)
    assert short == (411 * 3 + 355) * MIB > 0.05 * V5E
    assert model_step.released_blocks(STAGE, V5E) == ()
    assert model_step.released_blocks(
        STAGE._replace(parameters=()), V5E) == everything
    assert model_step.released_blocks(
        STAGE._replace(parameters=STAGE.gradients), V5E) == everything
    # Twice the memory: what is uncounted is under the margin, and fits.
    assert model_step.released_blocks(STAGE, 2 * V5E) == everything


@pytest.mark.parametrize("name,row,want", [
    # (held + working) where the walk holds most, the gradients there in
    # the compute dtype, and the choice, of three accepted cells (MiB).
    ("kimi", ([2155, 2029, 2029, 1254, 2029], [344, 344, 344, 202, 344],
              [2819, 5260, 5260, 4485, 5260], [197, 199, 199, 179, 199],
              6895, 1280), (4,)),
    ("laguna", ([1093, 1450, 1450, 1450, 1320], [259, 324, 324, 324, 259],
                [1573, 3936, 3936, 3936, 3806], [152, 272, 272, 272, 256],
                7915, 784), (3, 4)),
    ("xing4", ([660, 951, 951, 951, 951], [144] * 5,
               [1318, 1698, 1698, 1698, 1698], [246, 247, 247, 247, 247],
               8690, 256), (0, 1, 2, 3, 4)),
])
def test_the_accepted_cells_slack_covers_their_gradients(name, row, want):
    """With every gradient twice its counted bytes (float32 parameters,
    bfloat16 products) the accepted stacks' choices are what they were:
    activations are most of what they hold, and the slack covers it."""
    *lists, fixed, head = row
    stack = model_step.Stack(
        *([size * MIB for size in sizes] for sizes in lists),
        fixed=fixed * MIB, head=head * MIB, head_stays=0,
        parameters=[2 * size * MIB for size in lists[3]])
    assert model_step.released_blocks(stack, V5E) == want
    assert model_step.uncounted_bytes(stack, want) == 0
    assert model_step.released_blocks(
        stack._replace(parameters=()), V5E) == want
