"""Stamp machine-readable smoke-gate metrics into VERIFY_METRICS.json.

Each verify.sh smoke gate loads this file inside its heredoc
(``exec(open("scripts/verify_metrics.py").read())`` — the script cd's
to the repo root) and calls ``stamp("<gate>_smoke", {...})`` with the
numbers its assertions already computed: preempt MTTR, serve fill and
reply rate, autoscaler time-to-grow, SLO breach-detect latency and
MTTR. The leaves live under a top-level ``configs`` section, one entry
per gate; the sim gate reads ``load_smoke.knee_rps`` from it.

No-op when ``VERIFY_METRICS_PATH`` is unset (gates run standalone).
"""
import json
import os


def stamp(section, leaves):
    path = os.environ.get("VERIFY_METRICS_PATH")
    if not path:
        return
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        doc = {}
    doc.setdefault("configs", {})[section] = {
        k: (round(v, 6) if isinstance(v, float) else v)
        for k, v in leaves.items()
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
