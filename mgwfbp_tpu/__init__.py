"""mgwfbp_tpu — a TPU-native distributed training framework with the
capabilities of HKBU-HPML/MG-WFBP (Merged-Gradient Wait-Free BackPropagation).

The reference (/root/reference) implements MG-WFBP as PyTorch autograd hooks
feeding Horovod/NCCL async allreduces (distributed_optimizer.py). This package
re-designs the same capability for TPU: an alpha-beta communication cost model
plus measured layer-wise backward times drive a merge schedule
(`parallel.solver`) whose groups are lowered to bucketed `jax.lax.psum`
collectives inside a `shard_map`-ped train step (`parallel.allreduce`), so
XLA's latency-hiding scheduler overlaps each group's all-reduce with the
remaining backward compute.

Layer map (mirrors SURVEY.md §1):
  - CLI/launchers      scripts/, train CLI (reference: dist_mpi.sh, single.sh)
  - Config             mgwfbp_tpu.config (reference: settings.py + exp_configs)
  - Training drivers   mgwfbp_tpu.train_cli / trainer (dist_trainer.py, dl_trainer.py)
  - MG-WFBP scheduler  mgwfbp_tpu.parallel.{solver,buckets,allreduce}
                       (distributed_optimizer.py)
  - Cost models        mgwfbp_tpu.parallel.costmodel, mgwfbp_tpu.profiling
                       (profiling.py, utils.py)
  - Communication      jax.lax collectives over the ICI/DCN mesh
                       (horovod.torch.mpi_ops / NCCL / OpenMPI)
"""

import time as _time

# where the `import` span of the set-up record starts (telemetry/phases.py)
_IMPORT_T0 = _time.perf_counter()

from mgwfbp_tpu.version import __version__  # noqa: E402

__all__ = ["__version__"]
