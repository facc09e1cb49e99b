"""Core library: the paper's Byzantine Gradient Descent as composable pieces.

Public API:
    geometric_median, geometric_median_pytree, trim_weights
    aggregators.get_aggregator / available
    byzantine.get_attack / available / sample_byzantine_mask
    RobustConfig, make_robust_train_step, per_worker_grads, aggregate
    TrainState, init_train_state, advance, save/restore_train_state
    staleness: StalenessBuffer, make_arrival / available_arrivals
    grouping.make_grouping / choose_num_batches
    theory: paper constants & closed forms
"""

from repro.core.geometric_median import (  # noqa: F401
    geometric_median,
    geometric_median_pytree,
    trim_weights,
    batch_mean_norms,
    weiszfeld_step,
)
from repro.core import (  # noqa: F401
    aggregators, byzantine, grouping, staleness, theory)
from repro.core.staleness import (  # noqa: F401
    ArrivalSchedule,
    StalenessBuffer,
    arrival_from_config,
    available_arrivals,
    init_buffer,
    make_arrival,
    merge_reports,
)
from repro.core.shard_aggregation import (  # noqa: F401
    ShardSpec,
    blocked_partial_sum,
)
from repro.core.robust_train import (  # noqa: F401
    RobustConfig,
    aggregate,
    aggregate_reported,
    make_robust_train_step,
    make_run_rounds,
    make_sharded_aggregate,
    per_worker_grads,
    schedule_from_config,
)
from repro.core.train_state import (  # noqa: F401
    TrainState,
    advance,
    append_history,
    history_rows,
    init_train_state,
    restore_train_state,
    save_train_state,
)
