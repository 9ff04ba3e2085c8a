"""Job kind ``fit_window_heads``: ``fit_window`` for a model whose training
step has more heads than ``predict`` shows (a multi-token-prediction
module: ``predict`` returns the main logits, which
``harness.check_reference`` compares). The window, its metrics and its
facts are ``fit_window``'s own; after it, outside the window, the
configuration's ``check_heads`` compares what the program makes of the
check sequence through the other head and through its own loss with the
plain reference, and the verdicts join the run's ``checks``, which decide
``correct``. The numbers go to the notes as ``heads_check``.
"""
from __future__ import annotations


def run(ctx) -> dict:
    cell = ctx.cell
    result = cell.part("jobs", "fit_window").run(ctx)
    est = result["estimator"]
    ids = cell.model.check_batch(cell.sizes, cell.traffic, ctx.seed)
    checks, detail = cell.model.check_heads(
        est._model, est._state.params, ids, cell.sizes
    )
    ctx.note("heads_check", detail)
    result["checks"] = {**result.get("checks", {}), **checks}
    return result
