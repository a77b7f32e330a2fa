from repro.serve.steps import make_decode_step, make_prefill_step
from repro.serve.solve import (
    AdmissionPolicy,
    SolveEngine,
    SolveRequest,
    make_batched_solve_step,
)

__all__ = [
    "make_decode_step",
    "make_prefill_step",
    "AdmissionPolicy",
    "SolveEngine",
    "SolveRequest",
    "make_batched_solve_step",
]
