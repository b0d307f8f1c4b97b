"""Core library: the paper's medium-granularity SpTRSV dataflow in JAX.

Contains the custom compiler (node allocation + edge-granular scheduling +
psum caching + ICR + bank model), the coarse/fine baseline dataflows, the
branch-free VLIW executors, and the benchmark-matrix suite.
"""

from . import api, compiler, dag, frontends, matrices, serve  # noqa: F401
from .compiler import ComputeDag, compile_dag, compile_program  # noqa: F401
from .csr import TriCSR, UpperCSR, serial_solve, serial_solve_upper  # noqa: F401
from .program import AccelConfig, Program, ScheduleStats  # noqa: F401
from .executor import (  # noqa: F401
    execute_jax,
    execute_numpy,
    make_jax_executor,
    pad_batch,
)
from .fine import FineConfig, schedule_fine  # noqa: F401
