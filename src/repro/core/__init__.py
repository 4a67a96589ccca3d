"""repro.core — the paper's contribution: kernel-level DVFS planning for
waste reduction (strict/relaxed), vs pass-level and vs EDP."""
from .freq import AUTO, ClockPair, FrequencyGrid, paper_grid_3080ti, \
    tpu_v5e_grid
from .power_model import Chip, KernelSpec, get_chip, rtx3080ti_like, \
    a4000_like, tpu_v5e_like, CHIPS
from .workload import (WorkloadBuilder, build_workload, workload_totals,
                       decode_slot_buckets, decode_bucket_workloads,
                       train_phase_of, TRAIN_PHASES,
                       calibrate_workload_against_hlo)
from .measure import Campaign, MeasurementTable, NoiseModel
from .objectives import WastePolicy, edp, ed2p, compute_waste, pct
from .planner import (Plan, local_plan, global_plan, global_plan_dp,
                      pass_level_plan, edp_local_plan, edp_global_plan,
                      edp_pass_plan)
from .coalesce import CoalescedPlan, coalesced_global_plan, expand_sequence
from .search import search_plan, SearchReport, evaluate_against_truth
from .schedule import DVFSSchedule, ScheduleEntry, schedule_from_plan, \
    schedule_from_coalesced

__all__ = [
    "AUTO", "ClockPair", "FrequencyGrid", "paper_grid_3080ti",
    "tpu_v5e_grid", "Chip", "KernelSpec", "get_chip", "rtx3080ti_like",
    "a4000_like", "tpu_v5e_like", "CHIPS", "WorkloadBuilder",
    "build_workload", "workload_totals", "Campaign", "MeasurementTable",
    "NoiseModel", "WastePolicy", "edp", "ed2p", "compute_waste", "pct",
    "Plan", "local_plan", "global_plan", "global_plan_dp",
    "pass_level_plan", "edp_local_plan", "edp_global_plan", "edp_pass_plan",
    "CoalescedPlan", "coalesced_global_plan", "expand_sequence",
    "DVFSSchedule", "ScheduleEntry", "schedule_from_plan",
    "schedule_from_coalesced", "search_plan", "SearchReport",
    "evaluate_against_truth", "decode_slot_buckets",
    "decode_bucket_workloads", "train_phase_of", "TRAIN_PHASES",
    "calibrate_workload_against_hlo",
]
