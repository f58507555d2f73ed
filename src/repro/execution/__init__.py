"""Pluggable executor backends for MoE dispatch (plan/execute split).

See execution/base.py for the `DispatchPlan` / `Executor` contract and
DESIGN.md §6 for the design.  Importing this package registers the three
built-in executors: ``pallas``, ``xla``, ``dense``.
"""
from repro.execution.base import (DispatchPlan, Executor,  # noqa: F401
                                  available_executors, combine_scale_rows,
                                  execute, get_executor, plan_dispatch,
                                  plan_schedule, register_executor,
                                  router_aux_losses)
from repro.execution.dense import DenseExecutor  # noqa: F401
from repro.execution.pallas import PallasExecutor  # noqa: F401
from repro.execution.xla import (XlaExecutor, fused_gate_up_xla,  # noqa: F401
                                 grouped_gemm_xla)
