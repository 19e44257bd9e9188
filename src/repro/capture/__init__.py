"""Program capture: jaxpr-traced GEMM/chain discovery + planning IR.

Front end for planning *arbitrary jax programs*: trace a callable
(`trace`), dedupe its contraction sites and fusable chains into the
unified :class:`PlanProgram` IR (`program`), and lower the IR through
the batch planner in one pass (`plan`).  ``reference`` holds the
LlmSpec reference programs whose capture is differentially tested
against the hand-enumerated ``core.workloads`` tables.
"""
from .plan import (ProgramPlan, capture_model_decode,
                   capture_model_prefill, capture_serving_program,
                   plan_program)
from .program import (PlanProgram, ProgramChain, ProgramGemm,
                      captured_program, diff_programs, programs_equal)
from .reference import (capture_spec_decode, capture_spec_prefill,
                        capture_spec_scenario)
from .trace import CaptureResult, ChainSite, GemmSite, capture, harvest_jaxpr

__all__ = [
    "CaptureResult", "ChainSite", "GemmSite", "PlanProgram",
    "ProgramChain", "ProgramGemm", "ProgramPlan", "capture",
    "capture_model_decode", "capture_model_prefill",
    "capture_serving_program", "capture_spec_decode",
    "capture_spec_prefill", "capture_spec_scenario",
    "captured_program", "diff_programs", "harvest_jaxpr", "plan_program",
    "programs_equal",
]
