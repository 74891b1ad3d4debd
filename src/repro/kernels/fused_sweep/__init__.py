from repro.kernels.fused_sweep.ops import (default_interpret,  # noqa: F401
                                           fused_sweep_cells,
                                           fused_sweep_ragged,
                                           fused_sweep_tokens,
                                           fused_smem_bytes,
                                           fused_vmem_bytes)
