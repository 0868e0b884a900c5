"""quest_tpu: a TPU-native full-state quantum circuit simulator.

A ground-up JAX/XLA re-design with the full capability surface of QuEST
(the Quantum Exact Simulation Toolkit): state-vectors and density matrices,
~140 API functions (unitaries, decoherence channels, calculations, operators,
QASM logging), distribution via ``jax.sharding`` over TPU meshes instead of
MPI, and kernels expressed as XLA-fusable tensor programs instead of
hand-written loops.

Public names match the reference C API (``hadamard``, ``controlledNot``,
``calcFidelity``, ...) so a QuEST program ports by swapping includes for
imports; see README for the idiomatic-JAX functional layer underneath.

Architecture map (reference -> here):
  QuEST.h / QuEST.c (L5 API)      -> this package's top-level modules
  QuEST_validation.c (L4a)        -> validation.py
  QuEST_qasm.c (L4b)              -> qasm.py
  mt19937ar.c (L4c RNG)           -> numpy MT19937 in environment.py
  QuEST_common.c (L3 algorithms)  -> matrices.py + per-module logic
  QuEST_internal.h (L2 contract)  -> ops/ (pure jitted kernels)
  QuEST_cpu*.c / QuEST_gpu*.cu    -> ops/* via XLA (one backend, all targets)
  MPI exchange (L1 distributed)   -> parallel/ + XLA SPMD collectives
"""

import sys as _sys
import time as _time

#: the package's own import is timed where it runs (the gauge
#: ``quest_tpu_import_seconds``, set on the last line): a process that
#: imported JAX first -- ``jax`` in ``sys.modules`` here -- reads the
#: package alone, any other JAX's import with it (``jax_included`` = 1)
_IMPORT_T0 = _time.perf_counter()
_IMPORT_JAX = "jax" not in _sys.modules

from .datatypes import (  # noqa: F401
    PAULI_I, PAULI_X, PAULI_Y, PAULI_Z,
    DiagonalOp, PauliHamil, SubDiagonalOp, Vector,
    bindArraysToStackComplexMatrixN, bitEncoding,
    createComplexMatrixN, createPauliHamil, createPauliHamilFromFile,
    createSubDiagonalOp, destroyComplexMatrixN, destroyPauliHamil,
    destroySubDiagonalOp, getStaticComplexMatrixN, initComplexMatrixN,
    initPauliHamil, pauliOpType, phaseFunc,
)
from .environment import (  # noqa: F401
    QuESTEnv, createQuESTEnv, destroyQuESTEnv, getEnvironmentString,
    getQuESTSeeds, reportQuESTEnv, seedQuEST, seedQuESTDefault, syncQuESTEnv,
    syncQuESTSuccess,
)
from .registers import (  # noqa: F401
    Qureg, copyStateFromGPU, copyStateToGPU, copySubstateFromGPU,
    copySubstateToGPU, createCloneQureg, createDensityQureg, createQureg,
    destroyQureg, get_np,
)
from .validation import (  # noqa: F401
    QuESTError, invalidQuESTInputError, invalid_quest_input_error,
    set_input_error_handler,
)
from .circuits import Circuit  # noqa: F401
from .parallel.scheduler import explicit_mesh, plan_circuit  # noqa: F401
from .state_init import *  # noqa: F401,F403
from .gates import *  # noqa: F401,F403
from .calculations import *  # noqa: F401,F403
from .decoherence import *  # noqa: F401,F403
from .operators import *  # noqa: F401,F403
from .reporting import *  # noqa: F401,F403
from .checkpoint import (  # noqa: F401
    loadQureg, saveQureg, verify_snapshot, writeStateToCSV,
)
from . import profiling  # noqa: F401
from . import telemetry  # noqa: F401
telemetry.watch_jax_compiles()
from . import engine  # noqa: F401
from .engine import Engine, EnginePool, P, Param  # noqa: F401
from . import resilience  # noqa: F401
from .resilience import (  # noqa: F401
    QuESTBackpressureError, QuESTCancelledError, QuESTChecksumError,
    QuESTHangError, QuESTIntegrityError, QuESTPreemptionError,
    QuESTRetryError, QuESTTimeoutError, resume_segmented,
)
from . import channels  # noqa: F401
from . import trajectories  # noqa: F401
from .trajectories import (  # noqa: F401
    applyTrajectoryKraus, ensemble_density, run_ensemble, unravel,
)
from . import sampling  # noqa: F401
from .sampling import (  # noqa: F401
    applyMidCollapse, applyMidMeasurement, sampleQureg, sample_request,
)
from . import gradients  # noqa: F401
from .gradients import gradient_executable, parameter_shift  # noqa: F401

__version__ = "0.1.0"

telemetry.set_gauge("quest_tpu_import_seconds",
                    _time.perf_counter() - _IMPORT_T0,
                    jax_included=int(_IMPORT_JAX))
