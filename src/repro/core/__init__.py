"""PlinyCompute's primary contribution, adapted to JAX/TPU (DESIGN.md §2):

* the lambda calculus + Computation toolkit (paper §4),
* the TCAP IR + rule-based optimizer (paper §5, §7),
* the vectorized executor with PC's distributed join/aggregation plans
  (paper Appendix C/D),
* the sharding planner — the "declarative in the large" layer for the
  training/serving side.
"""
from repro.core.naming import NameScope, default_scope
from repro.core.lambdas import (LambdaArg, LambdaTerm, TypedLambdaArg,
                                UnknownColumnError, constant, make_lambda,
                                make_lambda_from_member,
                                make_lambda_from_method,
                                make_lambda_from_self, register_method,
                                METHOD_REGISTRY)
from repro.core.exprc import (EXPR_BACKENDS, FusedStage, build_steps,
                              kernel_cache_info, reset_kernel_cache)
from repro.core.computations import (AggregateComp, Computation, JoinComp,
                                     MultiSelectionComp, OrderByComp,
                                     ScanSet, SelectionComp, TopKComp,
                                     WriteSet)
from repro.core.tcap import TCAPOp, TCAPProgram, structural_signature
from repro.core.compiler import compile_graph
from repro.core.optimizer import (OptimizerReport, dead_column_elimination,
                                  eliminate_redundant_applies, optimize,
                                  push_filters_past_joins)
from repro.core.physical import PhysicalPlan, estimate_bytes, plan_physical
from repro.core.executor import ExecStats, Executor, NaiveExecutor
from repro.core.planner import ShardingPlan, make_plan
from repro.core.aggregates import AGG_KINDS, AggTerm, agg
from repro.core.dataset import Dataset, GroupedDataset, OrderedDataset
from repro.core.session import Session

__all__ = [
    "Dataset", "GroupedDataset", "OrderedDataset", "Session", "NameScope",
    "default_scope",
    "AGG_KINDS", "AggTerm", "agg",
    "structural_signature",
    "EXPR_BACKENDS", "FusedStage", "build_steps", "kernel_cache_info",
    "reset_kernel_cache", "TypedLambdaArg", "UnknownColumnError",
    "LambdaArg", "LambdaTerm", "constant", "make_lambda",
    "make_lambda_from_member", "make_lambda_from_method",
    "make_lambda_from_self", "register_method", "METHOD_REGISTRY",
    "AggregateComp", "Computation", "JoinComp", "MultiSelectionComp",
    "OrderByComp", "ScanSet", "SelectionComp", "TopKComp", "WriteSet",
    "TCAPOp", "TCAPProgram", "compile_graph", "OptimizerReport",
    "dead_column_elimination", "eliminate_redundant_applies", "optimize",
    "push_filters_past_joins", "PhysicalPlan", "estimate_bytes",
    "plan_physical", "ExecStats", "Executor", "NaiveExecutor",
    "ShardingPlan", "make_plan",
]
