"""One declarative parallelism plan (ROADMAP item 1, TorchTitan-style).

Every parallelism the framework runs — dp x tp x pp x sp x ep, plus the
multi-pod dp tier — composes from ONE :class:`ParallelPlan`: axis names,
per-axis sizes, the topology tier each axis rides (``ici`` inside a pod,
``dcn`` between pods), sharding presets, and legality rules.  Every CLI
flag resolves into a plan (:func:`plan_from_args`), ``parallel/mesh.py``
constructs the device mesh from it (:func:`make_mesh_from_plan` there),
and the ``sharding-legality`` / ``hardcoded-mesh-axis`` whole-program
analyses check call sites against the axis declaration in THIS module —
the plan is the single place an axis name, size, or tier can come from.

Axis order (outermost first) is part of the declaration::

    ('pod', 'data', 'expert', 'pipe', 'seq', 'model')

``model``/``seq`` are innermost so tensor- and sequence-parallel
collectives ride the fastest ICI links; ``pod`` is outermost and is the
ONLY axis that may ride DCN — a 25 GB/s link must never carry a
per-layer collective.  ``pod x data`` together form the data-parallel
tier: the global batch shards over both, and when ``pods > 1`` the
gradient reduction becomes two-level (``parallel/hierarchy.py``:
reduce-scatter/all-reduce inside the pod over ICI, cross-pod combine
over DCN on 1/pod_size of the bytes, ``--xpod-combine {sum,adasum}``).

Legality is checked BEFORE any mesh exists: a rejected plan raises a
named :class:`PlanLegalityError` carrying the violated rule, never an
opaque XLA shape error (tests/test_parallel_plan.py holds the
composition matrix).
"""

import dataclasses
import logging
from typing import Dict, Optional, Tuple

logger = logging.getLogger(__name__)

# ---------------------------------------------------------------------------
# axis declaration — THE canonical axis names.  parallel/mesh.py re-exports
# these for compatibility; everything outside parallel/ must import them
# (enforced by the hardcoded-mesh-axis lint rule).
# ---------------------------------------------------------------------------

POD_AXIS = "pod"
DATA_AXIS = "data"
MODEL_AXIS = "model"
SEQ_AXIS = "seq"
PIPE_AXIS = "pipe"
EXPERT_AXIS = "expert"

ALL_AXES = (POD_AXIS, DATA_AXIS, MODEL_AXIS, SEQ_AXIS, PIPE_AXIS, EXPERT_AXIS)

#: mesh construction order, outermost first (XLA lays device order so the
#: innermost axes ride the fastest ICI links; DCN carries the outermost)
MESH_AXIS_ORDER = (
    POD_AXIS, DATA_AXIS, EXPERT_AXIS, PIPE_AXIS, SEQ_AXIS, MODEL_AXIS,
)

#: topology tier per axis: 'dcn' (between pods, ~25 GB/s) or 'ici'
#: (inside a pod, ~200 GB/s).  Only the pod axis may cross DCN.
ICI_TIER = "ici"
DCN_TIER = "dcn"
AXIS_TIERS: Dict[str, str] = {
    POD_AXIS: DCN_TIER,
    DATA_AXIS: ICI_TIER,
    EXPERT_AXIS: ICI_TIER,
    PIPE_AXIS: ICI_TIER,
    SEQ_AXIS: ICI_TIER,
    MODEL_AXIS: ICI_TIER,
}

#: cross-pod gradient-combine modes (parallel/hierarchy.py)
XPOD_COMBINE_CHOICES = ("sum", "adasum")

#: KV-cache pool axis roles (serve/kv_cache.py; docs/serving.md,
#: "Incremental decode").  The page pools lay out as
#: ``(num_pages, n_layers, heads, page_size, head_dim)``: the page
#: dimension stays replica-local (each serve replica owns its own pool —
#: the fleet shards by request, not by page), and the HEAD dimension is
#: the one model-parallel cache axis, riding the same mesh axis the
#: attention heads already shard over.  Declared here so the
#: ``sharding-legality`` analysis accepts cache PartitionSpecs exactly
#: like any other axis use — the cache learns the plan's axes, it never
#: invents its own.
CACHE_HEAD_AXIS = MODEL_AXIS


class PlanLegalityError(ValueError):
    """A plan violated a named composition rule.  Raised at plan
    validation — before any mesh or XLA program exists — so the operator
    sees the rule, not a partitioner crash.  ``rule`` is the stable
    machine-readable name (the composition-matrix tests key on it)."""

    def __init__(self, rule: str, message: str):
        super().__init__(f"[{rule}] {message}")
        self.rule = rule


@dataclasses.dataclass(frozen=True)
class ParallelPlan:
    """The declarative composition of every parallelism dimension.

    Sizes are per-axis device counts; ``data=-1`` absorbs all remaining
    devices at mesh-construction time (the one late-bound size).
    ``pods`` splits the data-parallel tier across the DCN boundary:
    total dp = ``pods * data``, with ``data`` ranks inside each pod.
    """

    data: int = -1
    model: int = 1
    seq: int = 1
    pipe: int = 1
    expert: int = 1
    pods: int = 1
    #: cross-pod gradient combine: 'sum' (bit-identical to the flat
    #: all-reduce at pods=2, data=1) or 'adasum' (arXiv 2006.02924 —
    #: scale-adaptive, stabilizes the large effective batches multi-pod
    #: creates)
    xpod_combine: str = "sum"
    #: fixed f32 reduction order everywhere a reduction order is ours to
    #: choose: the cross-pod combine gathers and folds in pod-index
    #: order, the in-pod reduction gathers and folds in rank order, and
    #: the MoE expert combine replicates its token stream (the retired
    #: --moe-deterministic-reduction special case, now a plan property)
    deterministic_reductions: bool = False
    #: sequence-parallel strategy for the bert family ('ring'/'ulysses')
    seq_impl: str = "ring"

    # -- derived views ------------------------------------------------------

    @property
    def pod_size(self) -> int:
        """In-pod data-parallel size (the ICI half of the dp tier)."""
        return self.data

    @property
    def has_dcn(self) -> bool:
        """True when the plan declares a live DCN tier over dp."""
        return self.pods > 1

    def dp_axes(self) -> Tuple[str, ...]:
        """The mesh axes that together form the data-parallel tier, in
        mesh order — batch arrays shard over these."""
        return (POD_AXIS, DATA_AXIS)

    def axis_sizes(self) -> Dict[str, int]:
        return {
            POD_AXIS: self.pods,
            DATA_AXIS: self.data,
            EXPERT_AXIS: self.expert,
            PIPE_AXIS: self.pipe,
            SEQ_AXIS: self.seq,
            MODEL_AXIS: self.model,
        }

    def mesh_shape(self) -> Tuple[int, ...]:
        """Sizes in :data:`MESH_AXIS_ORDER` (``data`` may still be -1)."""
        sizes = self.axis_sizes()
        return tuple(sizes[a] for a in MESH_AXIS_ORDER)

    def tiers(self) -> Dict[str, str]:
        """axis name -> topology tier for the LIVE axes of this plan."""
        return {
            a: AXIS_TIERS[a]
            for a, n in self.axis_sizes().items()
            if n > 1 or (a == DATA_AXIS and n == -1)
        }

    def fixed_product(self) -> int:
        """Product of every axis size except ``data`` (the -1 absorber)."""
        return self.pods * self.model * self.seq * self.pipe * self.expert

    def kv_cache_axes(self, num_heads: int) -> Tuple[Optional[str], ...]:
        """Mesh axes of the paged KV pools, one entry per pool dimension
        ``(num_pages, n_layers, heads, page_size, head_dim)`` — pages
        replica-local, heads over :data:`CACHE_HEAD_AXIS` when the plan
        runs model parallelism.  This is the legality funnel for the
        cache: an indivisible head count is rejected HERE, by rule name,
        before any pool exists."""
        if self.model > 1 and num_heads % self.model != 0:
            raise PlanLegalityError(
                "cache-heads-indivisible",
                f"KV-cache pools shard {num_heads} heads over "
                f"{CACHE_HEAD_AXIS}={self.model}; the head count must "
                "divide the model-parallel size",
            )
        head_axis = CACHE_HEAD_AXIS if self.model > 1 else None
        return (None, None, head_axis, None, None)

    # -- legality -----------------------------------------------------------

    def validate(self, n_devices: Optional[int] = None) -> "ParallelPlan":
        """Check the composition rules; returns a plan with ``data``
        resolved when ``n_devices`` is given.  Every rejection is a
        :class:`PlanLegalityError` with a stable rule name."""
        for name, size in self.axis_sizes().items():
            if name == DATA_AXIS and size == -1:
                continue
            if size < 1:
                raise PlanLegalityError(
                    "non-positive-axis",
                    f"axis '{name}' has size {size}; every axis size must "
                    "be >= 1 (or data=-1 to absorb remaining devices)",
                )
        if self.xpod_combine not in XPOD_COMBINE_CHOICES:
            raise PlanLegalityError(
                "unknown-xpod-combine",
                f"--xpod-combine {self.xpod_combine!r} is not one of "
                f"{'/'.join(XPOD_COMBINE_CHOICES)}",
            )
        if self.seq_impl not in ("ring", "ulysses"):
            raise PlanLegalityError(
                "unknown-seq-impl",
                f"--seq-parallel-impl {self.seq_impl!r} is not one of "
                "ring/ulysses",
            )
        if self.seq > 1 and self.pipe > 1 and self.seq_impl == "ulysses":
            raise PlanLegalityError(
                "ulysses-pipeline-compose",
                "the ulysses (all-to-all) sequence-parallel strategy does "
                "not compose with the pipeline (docs/PARALLELISM.md); use "
                "--seq-parallel-impl ring for pp x sp",
            )
        plan = self
        if n_devices is not None:
            fixed = self.fixed_product()
            if self.data == -1:
                if n_devices % fixed != 0:
                    raise PlanLegalityError(
                        "indivisible-device-count",
                        f"device count {n_devices} is not divisible by "
                        f"pods*model*seq*pipe*expert={fixed}, so no 'data' "
                        "size can absorb the remainder",
                    )
                plan = dataclasses.replace(self, data=n_devices // fixed)
            elif self.data * fixed != n_devices:
                raise PlanLegalityError(
                    "device-count-mismatch",
                    f"plan {self.describe()} needs {self.data * fixed} "
                    f"devices but {n_devices} are visible",
                )
        return plan

    # -- presentation -------------------------------------------------------

    def describe(self) -> str:
        live = {
            a: n for a, n in self.axis_sizes().items()
            if n != 1
        }
        body = " ".join(f"{a}={n}" for a, n in live.items()) or "single-device"
        extras = []
        if self.has_dcn:
            extras.append(f"xpod={self.xpod_combine}")
        if self.deterministic_reductions:
            extras.append("deterministic")
        return f"ParallelPlan({body}{(' ' + ' '.join(extras)) if extras else ''})"

    def to_json(self) -> Dict:
        """The journal-facing form (telemetry kind ``comm-plan``)."""
        return {
            "axes": {a: n for a, n in self.axis_sizes().items()},
            "tiers": self.tiers(),
            "pods": self.pods,
            "pod_size": self.pod_size,
            "xpod_combine": self.xpod_combine,
            "deterministic_reductions": bool(self.deterministic_reductions),
        }


# ---------------------------------------------------------------------------
# CLI resolution — every flag funnels through here
# ---------------------------------------------------------------------------

_deterministic_shim_warned = False


def resolve_deterministic_reductions(args) -> bool:
    """``--deterministic-reductions`` is the plan property; the old
    MoE-only spelling ``--moe-deterministic-reduction`` is a deprecated
    alias that warns once and folds in."""
    global _deterministic_shim_warned
    det = bool(getattr(args, "deterministic_reductions", False))
    if getattr(args, "moe_deterministic_reduction", False):
        if not _deterministic_shim_warned:
            _deterministic_shim_warned = True
            logger.warning(
                "--moe-deterministic-reduction is deprecated; use "
                "--deterministic-reductions (a plan-wide property: fixed "
                "reduction order for the expert combine AND the two-level "
                "gradient reduction — docs/PARALLELISM.md, 'The plan')"
            )
        det = True
    return det


def plan_from_args(args) -> ParallelPlan:
    """Resolve the CLI flags into one validated (device-count-free)
    :class:`ParallelPlan` — THE funnel every parallelism flag passes
    through (mesh construction, the trainer, and the static analyses all
    read the plan, never the flags)."""
    plan = ParallelPlan(
        data=getattr(args, "data_parallel_size", -1) or -1,
        model=getattr(args, "model_parallel_size", 1) or 1,
        seq=getattr(args, "seq_parallel_size", 1) or 1,
        pipe=getattr(args, "pipeline_parallel_size", 1) or 1,
        expert=getattr(args, "expert_parallel_size", 1) or 1,
        pods=getattr(args, "num_pods", 1) or 1,
        xpod_combine=getattr(args, "xpod_combine", "sum") or "sum",
        deterministic_reductions=resolve_deterministic_reductions(args),
        seq_impl=getattr(args, "seq_parallel_impl", "ring") or "ring",
    )
    return plan.validate()


# ---------------------------------------------------------------------------
# the process-global plan (set alongside the global mesh)
# ---------------------------------------------------------------------------

_global_plan: Optional[ParallelPlan] = None


def set_global_plan(plan: Optional[ParallelPlan]) -> None:
    global _global_plan
    _global_plan = plan


def get_global_plan() -> Optional[ParallelPlan]:
    return _global_plan
