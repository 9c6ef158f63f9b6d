"""Core library: the paper's contribution — coded MPC (AGE-CMPC,
PolyDot-CMPC) — as composable JAX modules.

Layers (bottom-up):

* ``gf``             — GF(p) arithmetic (host oracle + f32-limb device path)
* ``powers``         — polynomial power-set combinatorics (sumsets, C1-C6)
* ``constructions``  — executable Algorithm 1 / Algorithm 2 share builders
* ``closed_form``    — Theorems 2 & 8 + baseline worker counts / overheads
* ``planner``        — CMPCPlan: evaluation points, interpolation matrices
* ``protocol``       — the 3-phase protocol engine (jit-able, vmapped)
* ``bw_decode``      — Berlekamp-Welch error-correcting Phase-3 decode
* ``distributed``    — shard_map execution over a worker mesh axis
* ``layers``         — secure_matmul (one, batched, CRT) / PrivateLinear:
                       synchronous high-level API
"""
from .bw_decode import (  # noqa: F401
    BWDecodeError,
    bw_decode_evals,
    bw_interpolate,
    bw_system_size,
)
from .closed_form import (  # noqa: F401
    CostPrediction,
    age_gamma,
    age_lambda_star,
    communication_overhead,
    computation_overhead,
    n_age,
    n_entangled,
    n_gcsa_na,
    n_polydot,
    n_ssmm,
    n_workers,
    predict,
    storage_overhead,
)
from .constructions import (  # noqa: F401
    Construction,
    PlanConfig,
    Scheme,
    age_cmpc,
    age_cmpc_fixed,
    build_scheme,
    get_construction,
    known_methods,
    polydot_cmpc,
    register_construction,
)
from .gf import Field, P_DEFAULT, mod_matmul_f32  # noqa: F401
