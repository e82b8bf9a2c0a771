"""repro.core — BIP-Based Expert Load Balancing (the paper's contribution).

Public surface:
  RouterConfig / init_router_state / route   — unified gate over the registry
  Balancer / register_balancer / get_balancer — pluggable strategy protocol
  bip_dual_update / bip_route_reference      — pure-jnp Algorithm 1/2 oracle
  OnlineBIPGate / ApproxBIPGate              — Algorithm 3 / 4 (streaming)
  balance_metrics / BalanceTracker           — MaxVio / AvgMaxVio / SupMaxVio
"""
from repro.core.approx import ApproxBIPGate
from repro.core.balancers import (
    Balancer,
    get_balancer,
    register_balancer,
    registered_balancers,
)
from repro.core.metrics import BalanceTracker, balance_metrics, expert_load, max_violation
from repro.core.online import OnlineBIPGate
from repro.core.ref_bip import (
    bisect_rounds,
    bip_dual_update,
    bip_dual_update_global,
    bip_dual_update_threshold,
    bip_route_reference,
    bip_topk,
    kth_largest,
    kth_largest_threshold,
)
from repro.core.router import DispatchPlan, compute_scores, make_dispatch_plan, route
from repro.core.types import RouterConfig, RouterOutput, init_router_state

__all__ = [
    "ApproxBIPGate",
    "Balancer",
    "BalanceTracker",
    "OnlineBIPGate",
    "RouterConfig",
    "RouterOutput",
    "balance_metrics",
    "get_balancer",
    "register_balancer",
    "registered_balancers",
    "bisect_rounds",
    "bip_dual_update",
    "bip_dual_update_global",
    "bip_dual_update_threshold",
    "bip_route_reference",
    "bip_topk",
    "compute_scores",
    "DispatchPlan",
    "expert_load",
    "init_router_state",
    "make_dispatch_plan",
    "kth_largest",
    "kth_largest_threshold",
    "max_violation",
    "route",
]
