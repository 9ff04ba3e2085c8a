"""The Kimi Linear style stack on the normal path, at tiny widths on the
CPU (hidden 64; Kimi Delta Attention with 4 heads of 16, 4-tap
convolutions and gates of rank 8 in layers 1 and 3, latent attention
without a query latent and without positions in layer 2; 16 experts of
width 32 of which a share is held beside a shared expert; sequence 128,
vocabulary 512), float32: the program against the benchmark's plain
reference, whose delta rule runs token by token (logits, loss, every
gradient), every departure the builder lists against its tolerance, the
mixer's parts against a few lines of ``jax.numpy`` each, latent attention
without a query latent and without positions against a loop over heads
and with Xing4.0's settings against what it gave before this PR, the
scopes, gauges and the one log line a built step leaves, and the older
families' modules untouched."""
import importlib.util
import logging
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raydp_tpu.models import CausalLM, granite_h_micro
from raydp_tpu.models import kda as kda_module
from raydp_tpu.models import latent as latent_module
from raydp_tpu.models import moe as moe_module
from raydp_tpu.models.kda import HeadGatedRMSNorm, KDAConfig
from raydp_tpu.models.latent import LatentAttention, LatentConfig
from raydp_tpu.models.mamba import CausalConv1d, GatedRMSNorm
from raydp_tpu.models.transformer import (
    MIXERS,
    YarnScaling,
    kimi_linear_48b_a3b,
    xing4_0,
)
from raydp_tpu.train.losses import lm_crossentropy
from raydp_tpu.utils.profiling import metrics
from tests.test_gdn import _run_once

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ = 128
SIZES = {
    "model_type": "kimi_linear", "vocab_size": 512, "hidden_size": 64,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "num_hidden_layers": 3, "first_k_dense_replace": 1, "head_dim": 16,
    "linear_attn_config": {
        "full_attn_layers": [2], "kda_layers": [1, 3], "head_dim": 16,
        "num_heads": 4, "short_conv_kernel_size": 4},
    "kda": {"gate_rank": 8, "chunk": 16}, "mla_use_nope": True,
    "model_max_length": 256, "rms_norm_eps": 1e-5, "rope_theta": 10000,
    "rope_scaling": None, "q_lora_rank": None, "kv_lora_rank": 16,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "num_experts": 4, "num_experts_routed": 16, "first_expert": 4,
    "num_experts_per_token": 2, "num_shared_experts": 1,
    "num_expert_group": 1, "topk_group": 1, "use_grouped_topk": True,
    "moe_router_activation_func": "sigmoid", "moe_renormalize": True,
    "routed_scaling_factor": 2.446, "moe_layer_freq": 1,
    "hidden_act": "silu", "tie_word_embeddings": False,
    "num_nextn_predict_layers": 0, "attention_impl": "dense", "remat": True,
    "compute_dtype": "float32", "param_dtype": "float32",
    "init": {"embedding_std": 1.0},
}
COLLECTIONS = ("params", moe_module.BUFFERS)


@pytest.fixture(scope="module")
def builder():
    """The benchmark's builder file: the plain reference lives there."""
    path = os.path.join(REPO, "benchmark", "configs", "kimi_delta_moe_lm.py")
    spec = importlib.util.spec_from_file_location("kimi_builder", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _init(model, *args):
    variables = _run_once(
        lambda: nn.unbox(model.init(jax.random.PRNGKey(0), *args)))
    return {k: variables[k] for k in COLLECTIONS if k in variables}


@pytest.fixture(scope="module")
def tiny(builder):
    """Model, seeded weights with decays strong enough that a 64-token
    chunk of the fastest channels passes -88 and single tokens pass the
    departure's clamp (``dt_bias`` + 2 over its published draw), ids."""
    model = CausalLM(builder.model_config(SIZES))
    ids = jnp.asarray(np.random.default_rng(0).integers(
        0, SIZES["vocab_size"], (1, SEQ)).astype(np.int32))
    variables = _init(model, ids)
    enc = variables["params"]["encoder"]
    for block in ("block_0", "block_2"):
        decay = enc[block]["kda"]["decay"]
        decay["dt_bias"] = decay["dt_bias"] + 2.0
    return model, variables, ids


def _rel(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


@pytest.fixture(scope="module")
def logits(builder, tiny):
    """(program, reference) logits of the seeded model."""
    model, variables, ids = tiny
    got = _run_once(
        lambda v: model.apply(v, ids, mutable=[moe_module.STATS])[0],
        variables)
    return got, _run_once(
        lambda v: builder.reference_logits(v, ids, SIZES), variables)


# ---------------------------------------------- program against reference

def test_parameter_tree_is_the_share(tiny):
    _, variables, _ = tiny
    tree = jax.tree_util.tree_map(lambda a: tuple(a.shape), variables)
    kda = {"ln_kda": {"scale": (64,)}, "ln_mlp": {"scale": (64,)}, "kda": {
        "q_proj": {"kernel": (64, 64)}, "k_proj": {"kernel": (64, 64)},
        "v_proj": {"kernel": (64, 64)},
        "conv": {n: {"kernel": (4, 64)} for n in "qkv"},
        "f_down": {"kernel": (64, 8)}, "f_up": {"kernel": (8, 64)},
        "decay": {"A_log": (4,), "dt_bias": (64,)},
        "beta": {"kernel": (64, 4)},
        "g_down": {"kernel": (64, 8)},
        "g_up": {"kernel": (8, 64), "bias": (64,)},
        "gate_norm": {"scale": (16,)}, "out": {"kernel": (64, 64)}}}
    # No q_down, no q_norm: one full-rank q projection under q_up's name.
    latent = {"ln_attn": {"scale": (64,)}, "ln_mlp": {"scale": (64,)},
              "attn": {"q_up": {"kernel": (64, 4, 24)},
                       "kv_down": {"kernel": (64, 24)},
                       "kv_norm": {"scale": (16,)},
                       "kv_up": {"kernel": (16, 4, 32)},
                       "out": {"kernel": (4, 16, 64)}}}
    dense = {"mlp_in": {"kernel": (64, 256)}, "mlp_out": {"kernel": (128, 64)}}
    routed = {"moe": {
        "router": {"kernel": (64, 16)}, "w_gate": (4, 64, 32),
        "w_up": (4, 64, 32), "w_down": (4, 32, 64),
        "shared": {"in": {"kernel": (64, 64)}, "out": {"kernel": (32, 64)}}}}
    assert tree["params"] == {
        "encoder": {
            "tok_embed": {"embedding": (512, 64)},
            "block_0": {**kda, **dense}, "block_1": {**latent, **routed},
            "block_2": {**kda, **routed}, "ln_final": {"scale": (64,)},
        },
        "lm_head": {"kernel": (64, 512)},
    }
    bias = {"moe": {"expert_bias": (16,)}}
    assert tree[moe_module.BUFFERS] == {
        "encoder": {"block_1": bias, "block_2": bias}}


def test_the_seeded_decays_are_strong(builder, tiny):
    """What the fixture promises: log-decays past the clamp and a chunk's
    cumulative sum past float32's exp range, so the comparison below sees
    the chunked form where a factored one would overflow."""
    _, variables, ids = tiny
    enc = variables["params"]["encoder"]
    p = enc["block_0"]["kda"]
    x = enc["tok_embed"]["embedding"][ids[0]]
    y = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5)
    f = (y @ p["f_down"]["kernel"]) @ p["f_up"]["kernel"]
    g = -jnp.exp(p["decay"]["A_log"])[:, None] * jax.nn.softplus(
        f + p["decay"]["dt_bias"]).reshape(SEQ, 4, 16)
    assert float(g.min()) < builder.CLAMP
    assert float(g.reshape(2, 64, 4, 16).sum(1).min()) < -88


def test_logits_match_the_plain_reference(logits):
    got, want = logits
    assert got.shape == (1, SEQ, SIZES["vocab_size"])
    assert _rel(got, want) < 2e-5


def test_loss_and_gradients_match_the_plain_reference(builder, tiny):
    model, variables, ids = tiny
    rest = {k: v for k, v in variables.items() if k != "params"}

    def loss(params):
        out = model.apply(
            {"params": params, **rest}, ids, mutable=[moe_module.STATS])[0]
        return lm_crossentropy(out, ids)

    got, grads = _run_once(jax.value_and_grad(loss), variables["params"])
    want, want_grads = _run_once(
        lambda v: builder.reference_loss_and_grads(v, ids, SIZES), variables)
    assert abs(float(got) - float(want)) < 1e-5
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    want_flat = dict(jax.tree_util.tree_flatten_with_path(
        want_grads["params"])[0])
    assert len(flat) == len(want_flat) == 60
    for path, g in flat:
        w = want_flat[path]
        assert float(jnp.max(jnp.abs(g - w))) <= 2e-4 * float(
            jnp.max(jnp.abs(w))) + 1e-8, jax.tree_util.keystr(path)


def test_the_departures_are_the_builders_list(builder):
    assert set(builder.DEPARTURES) == {
        "scalar_decay", "no_delta", "decay_clamped", "independent_chunks",
        "beta_one", "no_qk_l2norm", "no_short_conv", "no_output_gate",
        "rotary_on_latent", "gates_times_one"}
    assert set(builder.UNSEEN_ON_THE_CHIP) <= set(builder.DEPARTURES)
    with pytest.raises(ValueError, match="unknown departure"):
        builder.reference_logits({}, None, SIZES, depart="no_such_thing")


@pytest.mark.parametrize("depart", [
    "scalar_decay", "no_delta", "decay_clamped", "independent_chunks",
    "beta_one", "no_qk_l2norm", "no_short_conv", "no_output_gate",
    "rotary_on_latent", "gates_times_one",
])
def test_tolerance_refuses_a_departure_from_the_mathematics(
        builder, tiny, logits, depart):
    """Each departure moves the float32 reference's logits by more than
    the cell's tolerance, where the program's own are 1e-5 from it. The
    clamp of a log-decay at -5 cannot: it changes a decay factor by
    e^-5 = 0.7% at most, under any tolerance bf16 leaves room for; the
    float32 comparison here sees it a hundred times over its own error
    (and the chip's check lists it as unseen)."""
    _, variables, ids = tiny
    got, want = logits
    moved = _rel(_run_once(lambda v: builder.reference_logits(
        v, ids, SIZES, depart=depart), variables), want)
    if depart == "decay_clamped":
        assert moved > 100 * _rel(got, want)
        assert depart in builder.UNSEEN_ON_THE_CHIP
    else:
        assert moved > builder.TOLERANCE


def test_a_bfloat16_trunk_is_within_and_float8_outside(builder, tiny,
                                                       logits):
    _, variables, ids = tiny
    _, want = logits
    for trunk, inside in ((jnp.bfloat16, None), (jnp.float8_e4m3fn, False)):
        moved = _rel(_run_once(lambda v: builder.reference_logits(
            v, ids, SIZES, trunk=trunk), variables), want)
        if inside is False:
            assert moved > builder.TOLERANCE
        else:
            assert moved > 1e-4       # rounding is seen, whatever it reads


# ------------------------------------------------------ the mixer's parts

def test_head_gated_norm_is_not_mambas_gated_norm():
    """``rms_16(o_h) w * sigmoid(z_h)`` a head against three lines of jnp;
    Mamba-2's (the gate first, one norm over all features) gives another
    answer on the same inputs."""
    rng = np.random.default_rng(1)
    o = jnp.asarray(rng.standard_normal((2, 5, 4, 16)), jnp.float32)
    z = jnp.asarray(rng.standard_normal((2, 5, 64)), jnp.float32)
    norm = HeadGatedRMSNorm(1e-5, jnp.float32, jnp.float32)
    variables = norm.init(jax.random.PRNGKey(0), o, z)
    scale = jnp.asarray(rng.uniform(0.5, 1.5, 16), jnp.float32)
    variables = {"params": {"scale": scale}}
    want = o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True) + 1e-5) * scale
    want = want * jax.nn.sigmoid(z).reshape(o.shape)
    np.testing.assert_allclose(norm.apply(variables, o, z), want, rtol=1e-6,
                               atol=1e-6)
    other = GatedRMSNorm(1e-5, jnp.float32, jnp.float32)
    flat = o.reshape(2, 5, 64)
    theirs = other.apply(other.init(jax.random.PRNGKey(0), flat, z), flat, z)
    assert not np.allclose(theirs, want.reshape(2, 5, 64), atol=1e-2)


def test_the_convolution_without_a_bias_is_mambas_with_its_bias_zero():
    x = jnp.asarray(np.random.default_rng(2).standard_normal((2, 9, 8)),
                    jnp.float32)
    with_bias = CausalConv1d(4, jnp.float32, jnp.float32)
    variables = nn.unbox(with_bias.init(jax.random.PRNGKey(0), x))
    assert set(variables["params"]) == {"kernel", "bias"}     # Granite's
    without = CausalConv1d(4, jnp.float32, jnp.float32, use_bias=False)
    bare = nn.unbox(without.init(jax.random.PRNGKey(0), x))
    assert set(bare["params"]) == {"kernel"}
    zeroed = {"params": {"kernel": bare["params"]["kernel"],
                         "bias": jnp.zeros(8)}}
    np.testing.assert_array_equal(
        without.apply(bare, x), with_bias.apply(zeroed, x))
    # Token 0 sees zeros before it: only the last tap.
    np.testing.assert_allclose(
        without.apply(bare, x)[:, 0],
        jax.nn.silu(bare["params"]["kernel"][3] * x[:, 0]), rtol=1e-6)


def test_granites_modules_trace_to_the_programs_they_were():
    """The Mamba-2 mixer's jaxpr names a bias add after the convolution
    and the gate before the norm, as before: the new flag's default and
    the new norm left them alone."""
    from raydp_tpu.models.mamba import Mamba2Mixer

    cfg = granite_h_micro(
        n_layers=1, d_model=32, n_heads=2, n_kv_heads=1, d_ff=64,
        vocab_size=64, ssm_heads=2, ssm_head_dim=8, ssm_state=8,
        ssm_chunk=8, layer_types=("mamba",), dtype=jnp.float32,
    )
    x = jnp.ones((1, 8, 32))
    mixer = Mamba2Mixer(cfg)
    variables = nn.unbox(jax.eval_shape(
        lambda: mixer.init(jax.random.PRNGKey(0), x)))
    assert set(variables["params"]["conv"]) == {"kernel", "bias"}
    assert set(variables["params"]["gate_norm"]) == {"scale"}
    assert variables["params"]["gate_norm"]["scale"].shape == (16,)


# ------------------------------------------------------- latent attention

def _latent_cfg(**kw):
    defaults = dict(
        vocab_size=64, d_model=64, n_heads=4, n_layers=1, dense_layers=1,
        d_ff=128, max_len=64, dtype=jnp.float32, attention_impl="dense",
        latent=LatentConfig(q_rank=None, kv_rank=16, nope_dim=16, rope_dim=8,
                            v_dim=16),
    )
    defaults.update(kw)
    return kimi_linear_48b_a3b(**defaults)


def test_latent_attention_without_a_q_latent_or_positions():
    """Against a loop over heads: q from one projection, the shared key's
    8 extra features concatenated to every head's, nothing rotated."""
    cfg = _latent_cfg()
    x = jnp.asarray(np.random.default_rng(3).standard_normal((2, 12, 64)),
                    jnp.float32)
    layer = LatentAttention(cfg)
    variables = nn.unbox(jax.jit(layer.init)(jax.random.PRNGKey(1), x))
    p = variables["params"]
    assert set(p) == {"q_up", "kv_down", "kv_norm", "kv_up", "out"}
    got = jax.jit(layer.apply)(variables, x)
    q = jnp.einsum("bsd,dhk->bshk", x, p["q_up"]["kernel"])
    down = x @ p["kv_down"]["kernel"]
    c, k_s = down[..., :16], down[..., 16:]
    c = c / jnp.sqrt(jnp.mean(c * c, -1, keepdims=True) + 1e-5)
    kv = jnp.einsum("bsr,rhk->bshk", c * p["kv_norm"]["scale"],
                    p["kv_up"]["kernel"])
    causal = np.tril(np.ones((12, 12), bool))
    want = jnp.zeros_like(x)
    for h in range(4):
        k_h = jnp.concatenate([kv[:, :, h, :16], k_s], -1)
        scores = jnp.einsum("bqk,bsk->bqs", q[:, :, h], k_h) * 24 ** -0.5
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
        ctx = jnp.einsum("bqs,bsk->bqk", probs, kv[:, :, h, 16:])
        want = want + ctx @ p["out"]["kernel"][h]
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    # Without positions a causal layer still tells order (its mask), but a
    # rotation would change the answer: the two programs differ.
    turned = LatentAttention(_latent_cfg(positions="rotary"))
    assert not np.allclose(
        jax.jit(turned.apply)(variables, x), got, atol=1e-3)


def test_latent_attention_with_xing4s_settings_is_bit_equal():
    """What ``tests/data/latent_attention_parent_pr43.npz`` holds was
    computed by the parent commit's ``LatentAttention`` (q latent of 24
    with its norm, YaRN-rotated shared key) as one jitted call on the CPU."""
    saved = np.load(os.path.join(
        REPO, "tests", "data", "latent_attention_parent_pr43.npz"))
    cfg = xing4_0(
        vocab_size=512, d_model=64, n_heads=4, n_layers=1, dense_layers=1,
        d_ff=128, max_len=64, latent=LatentConfig(
            q_rank=24, kv_rank=16, nope_dim=16, rope_dim=8, v_dim=16,
            yarn=YarnScaling(factor=64.0, original_max_len=16,
                             beta_fast=32.0, beta_slow=1.0, mscale=1.0,
                             mscale_all_dim=1.0)),
        hyper=None, dtype=jnp.float32, attention_impl="dense")
    x = jnp.asarray(saved["x"])
    layer = LatentAttention(cfg)
    variables = nn.unbox(jax.jit(layer.init)(jax.random.PRNGKey(3), x))
    assert set(variables["params"]) == {
        "q_down", "q_norm", "q_up", "kv_down", "kv_norm", "kv_up", "out"}
    got = jax.jit(layer.apply)(variables, x)
    np.testing.assert_array_equal(np.asarray(got), saved["y"])


def test_latent_attention_refuses_learned_positions():
    with pytest.raises(ValueError, match="rotary or without"):
        layer = LatentAttention(_latent_cfg(positions="learned"))
        layer.init(jax.random.PRNGKey(0), jnp.ones((1, 4, 64)))


# --------------------------------------------- spans, gauges, the log line

SCOPES = ("q_proj", "k_proj", "v_proj", "conv", "decay", "beta", "scan",
          "gate_norm", "out")


@pytest.fixture(scope="module")
def lowered(tiny):
    model, variables, ids = tiny
    text = jax.jit(
        lambda v: model.apply(v, ids, mutable=[moe_module.STATS])[0]
    ).lower(variables).as_text(debug_info=True)
    return text


@pytest.mark.parametrize("scope", SCOPES)
def test_the_mixer_names_its_scopes(lowered, scope):
    """What the benchmark's part rules read: ``block_i/kda/<scope>`` in
    the lowered program's locations."""
    assert f"block_0/kda/{scope}" in lowered
    assert f"block_2/kda/{scope}" in lowered


def test_the_layers_norm_and_the_latent_layer_keep_their_scopes(lowered):
    assert "block_0/ln_kda" in lowered
    assert "block_1/attn/q_up" in lowered and "block_1/ln_attn" in lowered
    assert "block_1/kda" not in lowered and "q_down" not in lowered


@pytest.mark.parametrize("gauge,value", [
    ("kda/layers", 4), ("kda/heads", 32), ("kda/chunk", 64),
    ("kda/chunks_per_step", 1024),
    ("kda/state_bytes_per_sequence", 4 * 32 * 128 * 128 * 4),
    ("kda/scan_kernel_layers", 4),
    # The layers whose chunk states the two state kernels carry: the same
    # predicate, so the same count.
    ("kda/state_kernel_layers", 4),
    # 4 layers x 256 chunks x 32 heads x a float32 [64, 64] inverse.
    ("kda/kept_inverse_mib", 512),
    ("latent/rotary_dims", 0),
])
def test_the_reports_of_the_published_stack(gauge, value, caplog):
    cfg = kimi_linear_48b_a3b(n_layers=5)
    with caplog.at_level(logging.INFO, logger="raydp_tpu.models.kda"):
        kda_module.report(cfg, tokens_per_step=16384)
    latent_module.report(cfg)
    assert metrics.gauge_value(gauge) == value
    lines = [r.getMessage() for r in caplog.records
             if r.name == "raydp_tpu.models.kda"]
    assert len(lines) == 1
    line = lines[0]
    # The pattern, the head sizes, the chunk and which scan runs.
    assert "kda kda kda latent kda" in line
    assert "32 heads of 128 (q, k) and 128 (v)" in line
    assert "chunk 64 (1024 chunks a step)" in line
    assert kda_module.SCAN_IMPLEMENTATION in line and "ops/kda.py" in line
    # ... and which path a chunk's work takes at these shapes.
    assert line.endswith(kda_module.SCAN_PATHS[True])
    assert "kda_chunk_forward" in line and "kda_chunk_rebuild" in line
    # ... and what carries the state from chunk to chunk.
    assert "kda_state_forward" in line and "kda_state_backward" in line
    # ... and what its forward keeps for the backward.
    assert "the forward keeps 512 MiB of chunk inverses a sequence" in line


@pytest.mark.parametrize("sequence,chunk,layers,kept_mib", [
    (16384, 64, 4, 512), (8192, 64, 4, 256), (48, 16, 0, 0), (100, 4, 0, 0),
])
def test_the_kernel_gauge_follows_the_sequences_chunk(sequence, chunk, layers,
                                                      kept_mib, caplog):
    """A sequence that is no multiple of 64 runs in a smaller chunk, and
    that by the ``jax.numpy`` form, which keeps no inverse: the gauges and
    the line say what ``kda_chunked`` will be asked with. The kept
    inverses are ONE sequence's, whatever the step's batch."""
    cfg = kimi_linear_48b_a3b(n_layers=5)
    assert cfg.kda.scan_chunk(sequence) == chunk
    assert cfg.kda.scan_runs_kernels(sequence) is bool(layers)
    with caplog.at_level(logging.INFO, logger="raydp_tpu.models.kda"):
        kda_module.report(cfg, tokens_per_step=2 * sequence, sequence=sequence)
    assert metrics.gauge_value("kda/scan_kernel_layers") == layers
    assert metrics.gauge_value("kda/state_kernel_layers") == layers
    assert metrics.gauge_value("kda/kept_inverse_mib") == kept_mib
    assert cfg.kda.kept_inverse_bytes(4, sequence) == kept_mib << 20
    (record,) = [r for r in caplog.records
                 if r.name == "raydp_tpu.models.kda"]
    assert record.getMessage().endswith(kda_module.SCAN_PATHS[bool(layers)])
    assert f"keeps {kept_mib} MiB of chunk inverses" in record.getMessage()


def test_the_reports_read_zero_for_the_other_stacks(caplog):
    with caplog.at_level(logging.INFO, logger="raydp_tpu.models.kda"):
        for cfg in (xing4_0(n_layers=2), granite_h_micro(n_layers=2), None):
            kda_module.report(cfg, tokens_per_step=4096)
            for gauge in ("kda/layers", "kda/heads", "kda/chunk",
                          "kda/chunks_per_step", "kda/scan_kernel_layers",
                          "kda/state_kernel_layers",
                          "kda/kept_inverse_mib",
                          "kda/state_bytes_per_sequence"):
                assert metrics.gauge_value(gauge) == 0
    assert not caplog.records
    latent_module.report(xing4_0(n_layers=2))
    assert metrics.gauge_value("latent/rotary_dims") == 64
    latent_module.report(granite_h_micro(n_layers=2))
    assert metrics.gauge_value("latent/rotary_dims") == 0


def test_the_mixer_is_one_of_the_stacks_kinds():
    assert "kda" in MIXERS
    cfg = kimi_linear_48b_a3b()
    assert cfg.kda == KDAConfig() and cfg.positions == "none"
    assert cfg.latent.q_rank is None and cfg.latent.yarn is None
    with pytest.raises(NotImplementedError, match="ROADMAP R4"):
        CausalLM(kimi_linear_48b_a3b(n_layers=1)).apply(
            {}, jnp.ones((1, 4), jnp.int32), jnp.ones((1,), jnp.int32),
            method=CausalLM.prefill)
