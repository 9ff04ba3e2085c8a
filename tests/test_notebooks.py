"""The tutorial notebooks execute end-to-end (reference ships 2
notebooks, examples/pytorch_dlrm.ipynb + tensorflow_titanic.ipynb; its
CI never executes them — we do, cell by cell, in a subprocess)."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

NOTEBOOKS = ["dlrm_criteo.ipynb", "jax_titanic.ipynb"]
# A notebook's sizes as a reader runs them, and as this test does: the
# DLRM notebook is ``examples/dlrm_criteo.py``'s pipeline, which
# ``test_examples.py`` runs at its ``--smoke`` size (8,192 rows, 2 epochs);
# the cells run at that size here too (every cell, the same code).
SMOKE = {
    "dlrm_criteo.ipynb": [
        ("synthetic_criteo(20_000)", "synthetic_criteo(8_192)"),
        ("num_epochs=3", "num_epochs=2"),
    ],
}


@pytest.mark.parametrize("notebook", NOTEBOOKS)
def test_notebook_cells_execute(notebook):
    path = os.path.join(REPO, "examples", notebook)
    with open(path) as f:
        nb = json.load(f)
    cells = [
        "".join(c["source"])
        for c in nb["cells"]
        if c["cell_type"] == "code"
    ]
    script = "\n\n".join(cells) + "\nprint('NOTEBOOK-OK')\n"
    for as_written, as_run in SMOKE.get(notebook, ()):
        assert script.count(as_written) == 1, as_written
        script = script.replace(as_written, as_run)
    proc = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True,
        text=True,
        timeout=900,
        cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, (
        f"{notebook} failed\n--- stdout ---\n{proc.stdout[-2000:]}"
        f"\n--- stderr ---\n{proc.stderr[-3000:]}"
    )
    assert "NOTEBOOK-OK" in proc.stdout
