"""PCCL core: process group-aware collective algorithm synthesis (the paper's
contribution), plus the validation oracle, baselines, and the alpha-beta
simulator used for evaluation."""

from repro.core.algorithm import (
    CollectiveAlgorithm,
    Transfer,
    TransferColumns,
    TransferList,
)
from repro.core.conditions import (
    ChunkIds,
    Condition,
    ReduceCondition,
    all_gather,
    all_reduce,
    all_to_all,
    all_to_allv,
    broadcast,
    gather,
    multicast,
    point_to_point,
    reduce,
    reduce_scatter,
    scatter,
)
from repro.core.engine import (
    PhasePlan,
    PhaseSpec,
    SynthesisEngine,
    order_conditions,
)
from repro.core.errors import FabricDegradedError, PCCLError
from repro.core.hierarchy import HierarchicalSynthesizer, HierarchyError
from repro.core.repair import (
    DamageReport,
    DegradationEvent,
    PlanRepairer,
    RepairResult,
)
from repro.core.request import CollectiveRequest
from repro.core.traffic import CommSketch, SketchInfeasibleError, \
    TrafficEngineer
from repro.core.registry import (
    AlgorithmRegistry,
    canonicalize_group,
    default_registry,
    enumerate_automorphisms,
    is_automorphism,
    relabel_algorithm,
    topology_fingerprint,
)
from repro.core.simulator import (
    Flow,
    SimResult,
    collective_bandwidth,
    phase_breakdown,
    replay_algorithm,
    simulate_flows,
)
from repro.core.baselines import (
    direct_all_gather,
    direct_all_to_all,
    ring_all_gather,
    shortest_path_links,
)
from repro.core.translate import (
    PpermuteProgram,
    Send,
    from_msccl_json,
    to_msccl_json,
    to_ppermute_program,
)
from repro.core.planservice import PlanService
from repro.core.serialize import (
    load_plan_npz,
    plan_disk_bytes,
    save_plan_npz,
)

__all__ = [
    "CollectiveAlgorithm",
    "Transfer",
    "TransferColumns",
    "TransferList",
    "PlanService",
    "load_plan_npz",
    "plan_disk_bytes",
    "save_plan_npz",
    "SynthesisEngine",
    "PhasePlan",
    "PhaseSpec",
    "HierarchicalSynthesizer",
    "HierarchyError",
    "PCCLError",
    "FabricDegradedError",
    "CollectiveRequest",
    "DamageReport",
    "DegradationEvent",
    "PlanRepairer",
    "RepairResult",
    "CommSketch",
    "SketchInfeasibleError",
    "TrafficEngineer",
    "AlgorithmRegistry",
    "canonicalize_group",
    "default_registry",
    "enumerate_automorphisms",
    "is_automorphism",
    "relabel_algorithm",
    "topology_fingerprint",
    "ChunkIds",
    "Condition",
    "ReduceCondition",
    "all_gather",
    "all_reduce",
    "all_to_all",
    "all_to_allv",
    "broadcast",
    "gather",
    "multicast",
    "point_to_point",
    "reduce",
    "reduce_scatter",
    "scatter",
    "order_conditions",
    "Flow",
    "SimResult",
    "collective_bandwidth",
    "phase_breakdown",
    "replay_algorithm",
    "simulate_flows",
    "direct_all_gather",
    "direct_all_to_all",
    "ring_all_gather",
    "shortest_path_links",
    "PpermuteProgram",
    "Send",
    "from_msccl_json",
    "to_msccl_json",
    "to_ppermute_program",
]
