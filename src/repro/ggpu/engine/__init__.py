"""G-GPU execution engine: composable pipeline stages for the SIMT
cycle-approximate simulator.

Stage modules (boundaries documented in DESIGN.md):

  * ``config``    — ``GGPUConfig`` / ``ScalarConfig`` (jit-static knobs,
    including the ``memsys`` organization and ``fuse`` dispatch width)
  * ``frontend``  — fetch/decode (min-PC reconvergence), operand read,
    retire (writeback + PC advance)
  * ``alu``       — the PE integer datapath, shared with the Pallas twin in
    ``repro.kernels.pe_simd``
  * ``memsys``    — the ``MemorySystem`` protocol and cache organizations
    (``SharedCache``, ``BankedPerCUCache``)
  * ``scheduler`` — resident-wavefront selection and the lockstep-round
    cycle model
  * ``stepper``   — composition root: the jitted ``while_loop`` machine,
    fused dispatch, and the ``launch`` entry point (cohort or vmapped
    batch, picked from its input; ``run_kernel`` for one launch)
"""
from repro.ggpu.engine.alu import branch_taken, exec_alu, select_alu
from repro.ggpu.engine.config import GGPUConfig, ScalarConfig
from repro.ggpu.engine.memsys import (MEMSYS_REGISTRY, BankedPerCUCache,
                                      CacheResult, MemorySystem, SharedCache,
                                      get_memsys)
from repro.ggpu.engine.stepper import (KernelLaunchError, LaunchHandle,
                                       MachineState, Patch, cohort_rows,
                                       launch, launch_shards, run_kernel)

__all__ = [
    "GGPUConfig", "ScalarConfig", "MachineState", "KernelLaunchError",
    "LaunchHandle", "Patch", "cohort_rows", "launch", "launch_shards",
    "run_kernel",
    "exec_alu", "select_alu", "branch_taken",
    "MemorySystem", "SharedCache", "BankedPerCUCache", "CacheResult",
    "MEMSYS_REGISTRY", "get_memsys",
]
