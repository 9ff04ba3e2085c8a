"""Every example runs end-to-end in --smoke mode (the reference CI runs
each example script after pytest — .github/workflows/raydp.yml:107-116)."""
import os
import subprocess
import sys

import pytest

EXAMPLES = [
    "data_process.py",
    "jax_nyctaxi.py",
    "torch_nyctaxi.py",
    "tf_nyctaxi.py",
    "jax_titanic.py",
    "dlrm_criteo.py",
    "bert_glue.py",
    "olmoe_finetune.py",
    "granite_finetune.py",
    "lfm2_finetune.py",
    "gbt_nyctaxi.py",
    "spmd_job.py",
    "pod_driver.py",
]

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("example", EXAMPLES)
def test_example_smoke(example):
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", example), "--smoke"],
        capture_output=True,
        text=True,
        timeout=600,
        cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, (
        f"{example} failed\n--- stdout ---\n{proc.stdout[-3000:]}"
        f"\n--- stderr ---\n{proc.stderr[-3000:]}"
    )
    assert "OK" in proc.stdout
