"""TPU-native distributed-training framework.

A brand-new JAX/XLA framework with the capabilities of the CS744 PyTorch
distributed tutorial (reference: kkyyhh96/CS744_PyTorch_Distributed_Tutorial).
The reference is four progressively more automated implementations of
data-parallel SGD training of VGG-11 on CIFAR-10 over 4 ranks
(gather/scatter, p2p star, allreduce, DDP). This framework re-expresses
that as ONE single-program SPMD engine with pluggable gradient-sync
strategies running over a `jax.sharding.Mesh`:

- the reference's master/slave dual source trees (rank asymmetry as two
  parallel file trees) become single-program `shard_map` code where rank
  asymmetry, where needed, is `lax.axis_index` arithmetic;
- Gloo collectives over TCP become XLA collectives over ICI/DCN
  (`psum`, `all_gather`, `ppermute`);
- `torch.distributed.init_process_group` becomes
  `jax.distributed.initialize`;
- tape autograd + DDP's C++ reducer become `jax.grad` inside one jitted
  step, with XLA's latency-hiding scheduler providing the compute/comm
  overlap DDP's bucketing provides.
"""

__version__ = "0.1.0"

from cs744_pytorch_distributed_tutorial_tpu.config import TrainConfig

__all__ = ["TrainConfig", "__version__"]
