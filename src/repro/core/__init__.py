"""Core: the paper's contribution — parallel Quick Sort on the OHHC.

Modules: topology (OHHC graph), schedule (3-phase accumulation + Theorem-3
accounting), partition (Array Division Procedure + balanced splitters),
ohhc_sort (paper-faithful sort + counters + cost model), sample_sort
(beyond-paper models), dist_sort (shard_map mesh implementation), engine
(the unified autotuned dispatch layer over all three paths, DESIGN.md §4),
workloads (host arithmetic behind the engine's top-k / pytree pairs /
streaming-merge operations, DESIGN.md §12).
"""

from repro.core.topology import OHHCTopology, table_1_1, HHC_SIZE
from repro.core.schedule import AccumulationSchedule, payload_bytes_per_round
from repro.core.partition import (
    bucketed_length,
    default_capacity,
    pack_segments,
    paper_bucket_ids,
    sampled_splitters,
    splitter_bucket_ids,
    bucket_counts,
    bucket_ranks,
    scatter_to_buckets,
    unpack_segments,
    unscatter,
)
from repro.core.ohhc_sort import (
    LinkModel,
    ohhc_sort_sim,
    ohhc_sort_host,
    quicksort_counters,
    parallel_quicksort_counters,
    bitonic_counters,
    model_comm_time_s,
)
from repro.core.dist_sort import dist_sort, host_check_globally_sorted
from repro.core.workloads import (
    WORKLOAD_OPS,
    TopKTooLarge,
    host_bucket_ids,
    host_top_k,
    merge_sorted_arrays,
    topk_cut,
)
from repro.core.engine import (
    SEGMENT_BITONIC_MAX,
    InputStats,
    SortEngine,
    SortPlan,
    autotune_capacity,
    choose_batch_plan,
    choose_plan,
    estimate_batch_stats,
    estimate_stats,
    x64_enabled,
)

__all__ = [
    "SEGMENT_BITONIC_MAX",
    "InputStats",
    "SortEngine",
    "SortPlan",
    "autotune_capacity",
    "choose_batch_plan",
    "choose_plan",
    "estimate_batch_stats",
    "estimate_stats",
    "x64_enabled",
    "OHHCTopology",
    "table_1_1",
    "HHC_SIZE",
    "AccumulationSchedule",
    "payload_bytes_per_round",
    "bucketed_length",
    "default_capacity",
    "pack_segments",
    "unpack_segments",
    "paper_bucket_ids",
    "sampled_splitters",
    "splitter_bucket_ids",
    "bucket_counts",
    "bucket_ranks",
    "scatter_to_buckets",
    "unscatter",
    "LinkModel",
    "ohhc_sort_sim",
    "ohhc_sort_host",
    "quicksort_counters",
    "parallel_quicksort_counters",
    "bitonic_counters",
    "model_comm_time_s",
    "dist_sort",
    "host_check_globally_sorted",
    "WORKLOAD_OPS",
    "TopKTooLarge",
    "host_bucket_ids",
    "host_top_k",
    "merge_sorted_arrays",
    "topk_cut",
]
