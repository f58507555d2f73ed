"""Pluggable schedule-construction policies for MoE dispatch.

See scheduling/base.py for the policy contract and registry;
DESIGN.md §3 for the design.  Importing this package registers the three
built-in policies: ``fixed``, ``capacity_factor``, ``dynamic``.
"""
from repro.scheduling.base import (DEFAULT_POLICY_SWEEP,  # noqa: F401
                                   BlockSchedule, ScheduleStats,
                                   available_policies, build_schedule,
                                   get_policy, policy_config_kwargs,
                                   register_policy, round_up,
                                   schedule_stats)
from repro.scheduling.capacity import (build_capacity_schedule,  # noqa: F401
                                       capacity_slots, expert_capacity)
from repro.scheduling.dynamic import (build_dynamic_schedule,  # noqa: F401
                                      dynamic_capacity, sub_block)
from repro.scheduling.fixed import (build_fixed_schedule,  # noqa: F401
                                    schedule_capacity)
