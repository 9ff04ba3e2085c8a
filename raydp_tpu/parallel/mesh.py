"""Device-mesh specification for SPMD parallelism.

TPU-first replacement for the reference's flat data-parallel world
(reference: python/raydp/torch/estimator.py:276-278 — Ray Train worker count
is the only parallelism knob). Here a single ``MeshSpec`` names every
parallelism axis and builds a ``jax.sharding.Mesh`` over real TPU devices or
a virtual CPU mesh for tests:

  * ``dp`` — data parallel (batch dimension; gradients psum here)
  * ``pp`` — pipeline parallel (layer stages; ppermute microbatches)
  * ``sp`` — sequence/context parallel (ring attention over this axis)
  * ``tp`` — tensor parallel (weight shards; activations all-gather/psum)

Expert parallelism has no axis of its own. The logical axis ``expert``
lies on ``dp`` (``models/transformer.LOGICAL_RULES``), so on a ``dp`` mesh
a routed layer's stacked expert weights and their optimizer moments are
sharded over the data-parallel chips AT REST. What the layer computes with
them is the model's choice: told the axis (``TransformerConfig.state_axis``,
``MoEConfig.expert_axis`` with the mesh), the layer runs its expert
exchange inside a ``shard_map`` over that axis and every chip works on its
own ``n_experts / n`` experts (``models/moe.py:_exchanged``); told nothing,
the layer is one chip's program and XLA gathers every expert onto every
chip around the Mosaic grouped matmul, which it cannot partition. The
vocabulary's tables lie over the same ``state_axis``: the model computes
with them there, and ``JAXEstimator`` reads the axis from the model's
configuration and keeps them there at rest
(``models/transformer.vocab_rules``).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

AXIS_ORDER: Tuple[str, ...] = ("dp", "pp", "sp", "tp")

# Canonical logical-dimension → mesh-axis rules used by models in this repo.
# Models annotate arrays with logical dimension names; these rules lower them
# to PartitionSpecs (flax.linen.logical_to_mesh-style, but self-contained).
DEFAULT_LOGICAL_RULES: Tuple[Tuple[str, Optional[str]], ...] = (
    ("batch", "dp"),
    ("sequence", "sp"),
    ("hidden", None),
    ("embed", None),
    ("mlp", "tp"),
    ("heads", "tp"),
    ("kv", None),
    ("vocab", "tp"),
    ("expert", "dp"),
    ("stage", "pp"),
)


@dataclass(frozen=True)
class MeshSpec:
    """Named sizes for each parallelism axis; ``0``/missing means size 1.

    ``auto_from(n)`` factors a device count into a reasonable mesh when the
    user only says "use n chips".
    """

    dp: int = 1
    pp: int = 1
    sp: int = 1
    tp: int = 1

    def __post_init__(self):
        for name in AXIS_ORDER:
            if getattr(self, name) < 1:
                raise ValueError(f"mesh axis {name} must be >= 1")

    @property
    def size(self) -> int:
        return self.dp * self.pp * self.sp * self.tp

    @property
    def axis_sizes(self) -> Dict[str, int]:
        return {name: getattr(self, name) for name in AXIS_ORDER}

    @staticmethod
    def auto_from(n_devices: int, prefer: str = "dp") -> "MeshSpec":
        """All devices on one axis (default data-parallel)."""
        return MeshSpec(**{prefer: n_devices})

    def build(self, devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
        if devices is None:
            devices = jax.devices()
        if len(devices) < self.size:
            raise ValueError(
                f"mesh needs {self.size} devices ({self.axis_sizes}), "
                f"have {len(devices)}"
            )
        grid = np.asarray(devices[: self.size]).reshape(
            tuple(getattr(self, a) for a in AXIS_ORDER)
        )
        return Mesh(grid, AXIS_ORDER)


def named_sharding(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec(*spec))


def logical_to_spec(
    logical_axes: Sequence[Optional[str]],
    rules: Sequence[Tuple[str, Optional[str]]] = DEFAULT_LOGICAL_RULES,
    mesh: Optional[Mesh] = None,
) -> PartitionSpec:
    """Map logical dimension names to a PartitionSpec via the rule table.

    If ``mesh`` is given, axes whose mesh size is 1 are dropped (sharding
    over a trivial axis is a no-op but clutters lowering).
    """
    table = dict(rules)
    out = []
    for name in logical_axes:
        axis = table.get(name) if name is not None else None
        if axis is not None and mesh is not None and mesh.shape.get(axis, 1) == 1:
            axis = None
        out.append(axis)
    while out and out[-1] is None:
        out.pop()
    return PartitionSpec(*out)


def factor_devices(n: int) -> MeshSpec:
    """Factor ``n`` devices into a (dp, pp, sp, tp) mesh exercising every
    axis that fits: used by dry-run validation. Greedy: give tp and sp a
    factor of 2 first when available, pp next, rest to dp."""
    remaining = n
    sizes = {"tp": 1, "sp": 1, "pp": 1, "dp": 1}
    for axis in ("tp", "sp", "pp"):
        if remaining % 2 == 0 and remaining >= 2:
            sizes[axis] = 2
            remaining //= 2
    sizes["dp"] = remaining
    spec = MeshSpec(**sizes)
    assert spec.size == n
    return spec
