from raydp_tpu.models.mlp import MLP, binary_classifier, taxi_fare_regressor
from raydp_tpu.models.pipelined import PipelinedClassifier
from raydp_tpu.models.transformer import (
    CausalLM,
    SequenceClassifier,
    TransformerConfig,
    TransformerEncoder,
    bert_base,
    granite_h_micro,
    kimi_linear_48b_a3b,
    olmo_hybrid_7b,
    lfm2_8b_a1b,
    olmoe,
    param_shardings,
    tiny_transformer,
    laguna_xs_2,
    sdar_30b_a3b,
    keye_vl_2_0_30b_a3b,
    mellum2_12b_a2_5b,
    nemotron_3_nano_30b_a3b,
    ouro_2_6b,
    vocab_rules,
    xing4_0,
    glm_4_7_flash,
)
from raydp_tpu.models.blockdiff import BlockDiffusionConfig, BlockDiffusionLM
from raydp_tpu.models.loop import LoopLM
from raydp_tpu.models.mtp import MTPConfig, MTPLM
from raydp_tpu.models.sparse_index import SparseIndexConfig
from raydp_tpu.models.hyperconn import HyperConfig
from raydp_tpu.models.gdn import GDNConfig
from raydp_tpu.models.kda import KDAConfig
from raydp_tpu.models.latent import LatentConfig

from raydp_tpu.models.dlrm import (
    DLRM,
    DLRMConfig,
    PackedDLRM,
    ShardedEmbedding,
    criteo_dlrm,
    dlrm_shardings,
    tiny_dlrm,
)

from raydp_tpu.models.moe import (
    MoEClassifier,
    MoEConfig,
    MoELayer,
    moe_aux_loss,
    tiny_moe,
)

__all__ = [
    "PipelinedClassifier",
    "MoEClassifier",
    "MoEConfig",
    "MoELayer",
    "moe_aux_loss",
    "tiny_moe",
    "DLRM",
    "DLRMConfig",
    "PackedDLRM",
    "ShardedEmbedding",
    "criteo_dlrm",
    "dlrm_shardings",
    "tiny_dlrm",
    "MLP",
    "binary_classifier",
    "taxi_fare_regressor",
    "TransformerConfig",
    "TransformerEncoder",
    "SequenceClassifier",
    "CausalLM",
    "bert_base",
    "granite_h_micro",
    "kimi_linear_48b_a3b",
    "olmo_hybrid_7b",
    "lfm2_8b_a1b",
    "olmoe",
    "laguna_xs_2",
    "sdar_30b_a3b",
    "keye_vl_2_0_30b_a3b",
    "mellum2_12b_a2_5b",
    "nemotron_3_nano_30b_a3b",
    "ouro_2_6b",
    "LoopLM",
    "MTPConfig",
    "MTPLM",
    "glm_4_7_flash",
    "vocab_rules",
    "SparseIndexConfig",
    "xing4_0",
    "BlockDiffusionConfig",
    "BlockDiffusionLM",
    "HyperConfig",
    "KDAConfig",
    "GDNConfig",
    "LatentConfig",
    "tiny_transformer",
    "param_shardings",
]
