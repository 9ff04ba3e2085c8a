"""Statistics a layer sows on the device about one step, beside the loss:
the flax collection :data:`STATS`, which ``JAXEstimator`` asks for with
the step's ``mutable=`` state, merges over an epoch on the device and
fetches with the epoch's loss. It belongs to no layer: the routed layers
sow their expert counts into it (``models/moe.py``), the multi-stream
residual path how far its mixing matrix is from doubly stochastic
(``models/hyperconn.py``), and each reports its own names at the epoch's
end.

What a LOSS knows about a step (a head that runs in the loss, on what the
apply returned, has its parts there and no module to sow them from) it
notes with :func:`note`; :func:`step_stats` takes the notes of its step
with what the layers sowed.

A sower declares, once and where it is imported, how two values of its
statistic become one (two layers' within a step, two steps' within an
epoch); every statistic is a count or a size, so zero starts either
reduction in use.
"""
from __future__ import annotations

import threading

import jax.numpy as jnp

# The collection keeps the name it had when only the routed layers sowed
# into it: callers that wrote the literal (``mutable=["moe_stats"]``, the
# benchmark's LFM2 test among them) still receive what a step sows.
STATS = "moe_stats"

_REDUCE: dict = {}
# ``{name: value}`` noted since the trace of a step began (a thread's own:
# a step is traced on the thread that builds it).
_NOTED = threading.local()


def declare(name: str, reduce=jnp.add) -> str:
    """``reduce(a, b)`` merges two values of the statistic ``name``."""
    if _REDUCE.setdefault(name, reduce) is not reduce:
        raise ValueError(f"statistic {name!r} is declared with another "
                         "reduction")
    return name


def sow(module, name: str, value) -> None:
    """Sow ``value`` of a declared statistic from inside ``module``."""
    module.sow(
        STATS, name, value, reduce_fn=_REDUCE[name],
        init_fn=lambda: jnp.zeros_like(value),
    )


def note(name: str, value) -> None:
    """``value`` of a declared statistic, from OUTSIDE the model's apply
    and inside the same trace (a loss's parts). :func:`step_stats` of that
    trace returns it; :func:`begin_step` forgets what an earlier trace
    left (a step that asks for no statistics takes none)."""
    if name not in _REDUCE:
        raise ValueError(f"statistic {name!r} is not declared")
    _NOTED.__dict__.setdefault("values", {})[name] = value


def begin_step() -> None:
    """A step's trace begins (``models/step.apply_kwargs``): nothing is
    noted yet."""
    _NOTED.__dict__.pop("values", None)


def step_stats(variables) -> dict:
    """What one step's ``mutable=[STATS]`` state holds, as device values:
    each statistic merged over the layers that sowed it, and what the
    step's loss noted; ``{}`` for a model that sows none."""
    from flax.traverse_util import flatten_dict

    merged: dict = dict(_NOTED.__dict__.pop("values", {}))
    for path, value in flatten_dict(dict(variables.get(STATS, {}))).items():
        name = path[-1]
        merged[name] = _REDUCE[name](merged[name], value) if (
            name in merged
        ) else value
    return merged


def merge(a: dict, b: dict) -> dict:
    """Two steps' :func:`step_stats` as one (the epoch's running value)."""
    return {name: _REDUCE[name](a[name], b[name]) for name in a}
