"""The paper's primary contribution: group-based job scheduling (Packet
algorithm) with scale-ratio tuning, as a fixed-shape JAX discrete-event
simulation plus the pure policy functions reused by the ML-cluster layer."""
from repro.core import packet, precision
from repro.core.cohort import (CohortKey, WorkloadCohort, cohort_key,
                               group_workloads, stack_workloads)
from repro.core.des import (STEP_IMPLS, ChaosConfig, DesResult,
                            PackedWorkload, chaos_is_inert, chaos_uniforms,
                            event_budget, pack_workload, packet_scan_step,
                            resolve_max_requeues, resolve_ring,
                            simulate_packet, simulate_packet_host,
                            simulate_packet_reference, simulate_packet_scan,
                            simulate_packet_scan_lanes)
from repro.core.metrics import Metrics, efficiency_metrics
from repro.core.schedulers import (RIGID_SCHEDULERS, RigidCounts,
                                   simulate_backfill, simulate_fcfs,
                                   simulate_rigid)
from repro.core.sweep import (CHAOS_AXIS_FIELDS, PAPER_INIT_PROPS,
                              PAPER_SCALE_RATIOS, BaselineRun,
                              PlateauResult, chaos_axis_len, chaos_lane_grid,
                              cohort_lane_sharding, lane_padding,
                              lane_sharding, plateau_threshold, resolve_mode,
                              run_baselines, run_cohort_baselines,
                              run_cohort_grid,
                              run_packet_grid, run_window_oracle, sweep_plan)

__all__ = [
    "packet", "precision", "CohortKey", "WorkloadCohort", "cohort_key",
    "group_workloads", "stack_workloads", "ChaosConfig", "DesResult",
    "PackedWorkload", "chaos_is_inert", "chaos_uniforms", "event_budget",
    "pack_workload", "packet_scan_step", "STEP_IMPLS",
    "resolve_max_requeues", "resolve_ring", "simulate_packet",
    "simulate_packet_host", "simulate_packet_reference",
    "simulate_packet_scan", "simulate_packet_scan_lanes", "Metrics",
    "efficiency_metrics", "RIGID_SCHEDULERS", "RigidCounts",
    "simulate_backfill", "simulate_fcfs", "simulate_rigid",
    "CHAOS_AXIS_FIELDS", "PAPER_INIT_PROPS", "PAPER_SCALE_RATIOS",
    "BaselineRun", "PlateauResult",
    "chaos_axis_len", "chaos_lane_grid", "cohort_lane_sharding",
    "lane_padding", "lane_sharding", "plateau_threshold", "resolve_mode",
    "run_baselines", "run_cohort_baselines", "run_cohort_grid",
    "run_packet_grid",
    "run_window_oracle", "sweep_plan",
]
