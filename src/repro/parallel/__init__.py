from .sharding import (param_specs, param_shardings, batch_specs,
                       cache_specs, input_shardings, state_shardings,
                       spec_for_axes, data_axes, LOGICAL_RULES)
from .collectives import moe_all_to_all, moe_all_to_all_sharded
from .plan_transfer import (transfer_train_plan, transfer_serve_plan,
                            compare_transfer, TransferRow)

__all__ = [
    "param_specs", "param_shardings", "batch_specs", "cache_specs",
    "input_shardings", "state_shardings", "spec_for_axes", "data_axes",
    "LOGICAL_RULES", "moe_all_to_all", "moe_all_to_all_sharded",
    "transfer_train_plan", "transfer_serve_plan", "compare_transfer",
    "TransferRow",
]
