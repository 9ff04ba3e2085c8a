"""Shared by the benchmark's tests: where the benchmark lives, the tiny
configurations and cells of the CPU tests, and how a cell is added to a
temporary checkout the way a later PR adds one (files and entries only)."""
import copy
import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(REPO, "benchmark")

# float32 compute: at these widths the logits are small and bf16 rounding is
# a larger share of them than the chip tolerance allows at published widths.
TINY_CONFIGS = {
    "bert_tiny": {
        "builder": "bert_encoder_classifier",
        "vocab_size": 100, "hidden_size": 32, "num_hidden_layers": 2,
        "num_attention_heads": 2, "intermediate_size": 64,
        "max_position_embeddings": 16, "type_vocab_size": 2,
        "hidden_dropout_prob": 0.1, "num_classes": 2,
        "compute_dtype": "float32", "param_dtype": "float32",
        "attention_impl": "dense",
        "optimizer": {"name": "adamw", "learning_rate": 2e-5},
    },
    "dlrm_tiny": {
        "builder": "dlrm_packed",
        "dense_features": 4, "embed_dim": 8, "bottom_mlp": [16, 8],
        "top_mlp": [16, 8], "interaction": "dot",
        "vocab_sizes": [50, 200, 3, 1000], "embedding_impl": "take",
        "compute_dtype": "float32", "param_dtype": "float32",
        "optimizer": {"name": "adagrad", "learning_rate": 0.01},
    },
}
# tiny cell -> (the real cell it is a copy of, its configuration, changes)
TINY_CELLS = {
    "bert_tiny.fit": ("bert_base.fit_s128", "bert_tiny", {
        "seq_len": 16, "per_chip_batch": 8, "steps_per_epoch": 4,
        "data": {"generator": "glue_tokens", "seq_len": 16},
    }),
    "bert_tiny.fit_dp4": ("bert_base.fit_dp4", "bert_tiny", {
        "seq_len": 16, "per_chip_batch": 8, "steps_per_epoch": 4,
        "data": {"generator": "glue_tokens", "seq_len": 16},
    }),
    "dlrm_tiny.fit_staged": ("dlrm_kaggle.fit_staged", "dlrm_tiny", {
        "per_chip_batch": 64, "steps_per_epoch": 4,
    }),
    "dlrm_tiny.etl_fit": ("dlrm_kaggle.etl_fit", "dlrm_tiny", {
        "per_chip_batch": 64, "rows_per_job": 512,
        "staging": {"workers": 2, "partitions": 4, "shards": 2},
    }),
}


def add_cell(root: str, name: str, source: str, config: str, changes: dict):
    """Add the cell ``name`` to the tree at ``root`` the way a later PR
    does: a copy of ``source``'s workload file under the new name, with
    ``changes`` to its traffic, and entries in ``BENCHMARK.json``."""
    bench_path = os.path.join(root, "BENCHMARK.json")
    with open(bench_path) as f:
        bench = json.load(f)
    wdir = os.path.join(root, "benchmark", "workloads")
    with open(os.path.join(wdir, source + ".json")) as f:
        workload = json.load(f)
    workload["config"] = config
    workload["traffic"].update(changes)
    with open(os.path.join(wdir, name + ".json"), "w") as f:
        json.dump(workload, f)
    entry = copy.deepcopy(
        next(w for w in bench["workloads"] if w["name"] == source)
    )
    entry.update(name=name, config=config, traffic=name.split(".", 1)[1])
    bench["workloads"].append(entry)
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if source in metric.get("workloads", []):
            metric["workloads"].append(name)
    with open(bench_path, "w") as f:
        json.dump(bench, f)
