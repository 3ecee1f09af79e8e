"""Device-mesh management: axis planning, sharding rules, elastic re-mesh.

The reference implements no parallelism math — it orchestrates Megatron/
DeepSpeed (SURVEY.md §2.7). A TPU-native framework owns this layer: one
``Mesh`` whose named axes carry every strategy, with XLA GSPMD inserting the
collectives:

- ``dcn``  — data parallel ACROSS pod slices (outermost: traffic rides the
  data-center network, not ICI — only the once-per-step gradient
  all-reduce belongs here; the multi-slice "hybrid mesh" recipe)
- ``dp``   — pure data parallel (params replicated)
- ``fsdp`` — data parallel with fully-sharded params/opt state (ZeRO-3)
- ``sp``   — sequence/context parallel (ring attention axis, long context)
- ``tp``   — tensor parallel (innermost: highest-bandwidth ICI neighbors)
- ``ep``   — the expert group: the chips the experts' work is spread over
  (each holds every expert at a slice of its columns), and with it the
  vocabulary and attention's heads, as over ``tp``
- ``pp``   — pipeline stages (outer: least traffic between stages)

Elastic re-mesh policy: ``tp``/``pp``/``ep`` are fixed by the model shapes;
``dp × fsdp`` absorbs world-size changes (reference analogue: ElasticTrainer
keeps global batch fixed while DDP world changes, trainer.py:307 — here the
mesh itself re-forms and grad-accum rescales, trainer/elastic.py).
"""

from dataclasses import dataclass, field, replace
from typing import Dict, List, Optional, Tuple

import numpy as np

from dlrover_tpu.common.log import logger

# axis order: outermost (cheapest link, least traffic) → innermost
AXIS_ORDER = ("dcn", "pp", "dp", "fsdp", "ep", "sp", "tp")

# axes whose size is fixed by the model, not the cluster
MODEL_AXES = ("pp", "tp", "ep")


@dataclass(frozen=True)
class MeshPlan:
    """A concrete axis assignment for a device count."""

    axes: Dict[str, int] = field(default_factory=dict)

    @property
    def n_devices(self) -> int:
        n = 1
        for v in self.axes.values():
            n *= v
        return n

    def size(self, axis: str) -> int:
        return self.axes.get(axis, 1)

    @property
    def dp_total(self) -> int:
        """Number of data-parallel replicas of the batch axis
        (dcn × dp × fsdp: all shard the batch; fsdp additionally shards
        params within a slice)."""
        return self.size("dcn") * self.size("dp") * self.size("fsdp")

    def nontrivial_axes(self) -> List[str]:
        return [a for a in AXIS_ORDER if self.size(a) > 1]


def plan_mesh(
    n_devices: int,
    tp: int = 1,
    pp: int = 1,
    ep: int = 1,
    sp: int = 1,
    fsdp: Optional[int] = None,
    dp: Optional[int] = None,
    dcn: int = 1,
) -> MeshPlan:
    """Fill in dp/fsdp so the axis product covers ``n_devices``.

    Unspecified ``fsdp`` absorbs the remainder (ZeRO-style sharding is the
    TPU default — params live sharded in HBM); set ``fsdp=1, dp=None`` for
    pure replication. ``dcn`` = number of pod slices: every other axis
    lives within one slice (ICI); only the dcn gradient all-reduce crosses
    the data-center network.
    """
    if n_devices % dcn != 0:
        raise ValueError(
            f"n_devices={n_devices} not divisible by dcn={dcn} slices"
        )
    per_slice = n_devices // dcn
    fixed = tp * pp * ep * sp
    if per_slice % fixed != 0:
        raise ValueError(
            f"per-slice devices {per_slice} not divisible by "
            f"tp*pp*ep*sp={fixed}"
        )
    remainder = per_slice // fixed
    if fsdp is None and dp is None:
        fsdp, dp = remainder, 1
    elif fsdp is None:
        if remainder % dp != 0:
            raise ValueError(f"remainder {remainder} not divisible by dp={dp}")
        fsdp = remainder // dp
    elif dp is None:
        if remainder % fsdp != 0:
            raise ValueError(
                f"remainder {remainder} not divisible by fsdp={fsdp}"
            )
        dp = remainder // fsdp
    if dp * fsdp != remainder:
        raise ValueError(
            f"dp*fsdp={dp * fsdp} != remainder {remainder} "
            f"(n_devices={n_devices}, fixed={fixed})"
        )
    return MeshPlan(axes={
        "dcn": dcn, "pp": pp, "dp": dp, "fsdp": fsdp, "ep": ep, "sp": sp,
        "tp": tp,
    })


def build_mesh(plan: MeshPlan, devices: Optional[list] = None):
    """Materialize a jax Mesh from a plan.

    Axis order follows :data:`AXIS_ORDER` so ``tp`` lands on adjacent
    devices (contiguous device ids ≈ ICI neighbors on TPU slices)."""
    import jax
    from jax.sharding import Mesh

    devices = devices if devices is not None else jax.devices()
    if len(devices) < plan.n_devices:
        raise ValueError(
            f"plan needs {plan.n_devices} devices, have {len(devices)}"
        )
    dcn = plan.size("dcn")
    if dcn > 1:
        # slice-major ordering so the leading dcn axis maps whole slices:
        # every intra-slice axis then lives on ICI and only dcn crosses
        # the DCN (jax mesh_utils hybrid-mesh recipe). Pick per-slice
        # blocks from real slice_index groups when present — a dcn row
        # silently spanning physical slices would put fsdp/tp collectives
        # on the data-center network. Virtual/CPU devices carry no
        # slice_index — contiguous id blocks stand in for slices.
        per_slice = plan.n_devices // dcn
        groups: Dict[int, list] = {}
        for d in devices:
            groups.setdefault(getattr(d, "slice_index", None) or 0, []
                              ).append(d)
        if len(groups) > 1:
            full = [g for g in sorted(groups) if len(groups[g]) >= per_slice]
            if len(full) < dcn:
                raise ValueError(
                    f"plan wants dcn={dcn} slices of {per_slice} devices "
                    f"but only {len(full)} slices have enough "
                    f"({ {g: len(v) for g, v in sorted(groups.items())} }); "
                    "replan with a smaller dcn"
                )
            devices = [
                d for g in full[:dcn]
                for d in sorted(groups[g], key=lambda d: d.id)[:per_slice]
            ]
        else:
            devices = sorted(devices, key=lambda d: d.id)[: plan.n_devices]
    shape = tuple(plan.size(a) for a in AXIS_ORDER)
    dev_array = np.array(devices[: plan.n_devices]).reshape(shape)
    return Mesh(dev_array, AXIS_ORDER)


class ElasticMeshManager:
    """Re-plans the mesh when the world size changes (the TPU analogue of
    elastic DDP world re-formation)."""

    def __init__(self, tp: int = 1, pp: int = 1, ep: int = 1, sp: int = 1,
                 dcn: int = 1):
        self._tp, self._pp, self._ep, self._sp = tp, pp, ep, sp
        self._dcn = dcn
        self._plan: Optional[MeshPlan] = None

    @property
    def plan(self) -> Optional[MeshPlan]:
        return self._plan

    @property
    def min_unit(self) -> int:
        """Smallest usable device count — also the rendezvous ``node_unit``
        seed: worlds must keep dp×fsdp ≥ 1 with model axes intact."""
        return self._tp * self._pp * self._ep * self._sp

    def usable_devices(self, n_devices: int) -> int:
        return (n_devices // self.min_unit) * self.min_unit

    def replan(self, n_devices: int) -> MeshPlan:
        usable = self.usable_devices(n_devices)
        if usable == 0:
            raise ValueError(
                f"{n_devices} devices cannot host tp={self._tp} pp={self._pp} "
                f"ep={self._ep} sp={self._sp} (needs ≥ {self.min_unit})"
            )
        if usable != n_devices:
            logger.warning(
                "using %s of %s devices (world must be a multiple of %s)",
                usable, n_devices, self.min_unit,
            )
        # losing a whole pod slice shrinks dcn instead of failing: pick
        # the largest slice count ≤ the configured one that still divides
        # the usable world (dcn elasticity = reference node-group
        # elasticity, lifted to slices)
        dcn = self._dcn
        while dcn > 1 and usable % (dcn * self.min_unit) != 0:
            dcn -= 1
        self._plan = plan_mesh(
            usable, tp=self._tp, pp=self._pp, ep=self._ep, sp=self._sp,
            dcn=dcn,
        )
        logger.info("mesh plan for %s devices: %s", usable, self._plan.axes)
        return self._plan

    def apply_plan(self, plan: MeshPlan) -> None:
        """Adopt an externally re-planned decomposition (the world-cut
        planner, parallel/replan.py): the model axes it carries become
        the new fixed axes, so subsequent world-size replans keep the
        re-decomposed shape instead of the launch-time one."""
        self._tp = plan.size("tp")
        self._pp = plan.size("pp")
        self._ep = plan.size("ep")
        self._sp = plan.size("sp")
        self._dcn = plan.size("dcn")
        self._plan = plan
        logger.info("mesh plan adopted: %s", plan.axes)

    def build(self, devices: Optional[list] = None):
        if self._plan is None:
            import jax

            self.replan(len(devices) if devices is not None else
                        jax.device_count())
        return build_mesh(self._plan, devices)
