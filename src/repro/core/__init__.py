"""FARSI core: the paper's contribution (hybrid simulator + aware explorer).

Public API re-exports. See DESIGN.md §2 for the paper→TPU mapping.
"""
from .backend import (
    BackendStats,
    Candidate,
    JaxBatchedBackend,
    PythonBackend,
    SimHandle,
    SimTelemetry,
    SimulatorBackend,
    make_backend,
)
from .blocks import Block, BlockKind, make_accelerator, make_gpp, make_mem, make_noc
from .budgets import Budget, Distance, distance
from .campaign import Campaign, CampaignResult, RunSpec
from .codesign import CodesignLedger, FocusRecord
from .database import HardwareDatabase, TPUDatabase
from .design import Design
from .design_space import random_single_noc_designs
from .device_explore import (
    ChainBlockResult,
    ChainCarry,
    ChainRequest,
    DeviceChainRunner,
    MoveTable,
    reconcile_alloc,
)
from .event_sim import simulate_events
from .explorer import AWARENESS_LEVELS, ExplorationResult, Explorer, ExplorerConfig
from .gables import TaskRates, bottleneck_of, completion_time, phase_rates
from .phase_sim import SimResult, simulate
from .policy import (
    POLICIES,
    BottleneckRelaxation,
    DevCostPolicy,
    DeviceSA,
    FarsiPolicy,
    Focus,
    HeuristicPolicy,
    LocalityExploitation,
    NaiveSA,
    make_policy,
)
from .tdg import Task, TaskGraph, merge_graphs, workload_of
from .workloads import (
    Scenario,
    all_workloads,
    ar_complex,
    audio,
    calibrated_budget,
    cava,
    edge_detection,
    paper_budget,
    pulse_doppler,
    synthetic_family,
)

__all__ = [
    "BackendStats",
    "Block",
    "BlockKind",
    "Budget",
    "Campaign",
    "CampaignResult",
    "Candidate",
    "ChainBlockResult",
    "ChainCarry",
    "ChainRequest",
    "CodesignLedger",
    "Design",
    "DeviceChainRunner",
    "DeviceSA",
    "MoveTable",
    "SimHandle",
    "JaxBatchedBackend",
    "PythonBackend",
    "RunSpec",
    "SimulatorBackend",
    "Distance",
    "ExplorationResult",
    "Explorer",
    "ExplorerConfig",
    "FocusRecord",
    "HardwareDatabase",
    "SimResult",
    "TPUDatabase",
    "Task",
    "TaskGraph",
    "TaskRates",
    "AWARENESS_LEVELS",
    "POLICIES",
    "BottleneckRelaxation",
    "DevCostPolicy",
    "FarsiPolicy",
    "Focus",
    "HeuristicPolicy",
    "LocalityExploitation",
    "NaiveSA",
    "Scenario",
    "SimTelemetry",
    "all_workloads",
    "ar_complex",
    "audio",
    "bottleneck_of",
    "calibrated_budget",
    "cava",
    "completion_time",
    "distance",
    "edge_detection",
    "make_accelerator",
    "make_backend",
    "make_policy",
    "pulse_doppler",
    "synthetic_family",
    "make_gpp",
    "make_mem",
    "make_noc",
    "merge_graphs",
    "paper_budget",
    "phase_rates",
    "random_single_noc_designs",
    "simulate",
    "simulate_events",
    "workload_of",
]
