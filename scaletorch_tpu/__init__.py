"""scaletorch_tpu — a TPU-native 5D-parallelism LLM pretraining framework.

A from-scratch JAX/XLA/Pallas re-design of the capability surface of
jianzhnie/ScaleTorch (a pure-Python torch.distributed framework; see
/root/repo/SURVEY.md for the full structural analysis). The parallelism
dimensions — DP, TP, PP (AFAB + 1F1B), CP (ring attention), SP, and EP
(MoE all-to-all) — are expressed over a single ``jax.sharding.Mesh`` with
named axes ``('dp', 'pp', 'cp', 'ep', 'tp')``, with explicit XLA
collectives (``psum``, ``all_gather``, ``psum_scatter``, ``all_to_all``,
``ppermute``) inside ``shard_map`` where manual control wins, and GSPMD
sharding annotations where the compiler wins.

Reference parity map (reference file -> this package):
  scaletorch/parallel/process_group.py  -> scaletorch_tpu.parallel.mesh
  scaletorch/dist/                      -> scaletorch_tpu.ops.collectives
  scaletorch/parallel/tensor_parallel/  -> scaletorch_tpu.parallel.tensor_parallel
  scaletorch/parallel/pipeline_parallel/-> scaletorch_tpu.parallel.pipeline_parallel
  scaletorch/parallel/context_parallel/ -> scaletorch_tpu.ops.ring_attention,
                                           scaletorch_tpu.parallel.context_parallel
  scaletorch/parallel/sequence_parallel/-> scaletorch_tpu.parallel.sequence_parallel
  scaletorch/parallel/expert_parallel/  -> scaletorch_tpu.parallel.expert_parallel
  scaletorch/models/                    -> scaletorch_tpu.models
  scaletorch/trainer/                   -> scaletorch_tpu.trainer
  scaletorch/data/                      -> scaletorch_tpu.data
  scaletorch/utils/                     -> scaletorch_tpu.utils

Beyond the reference: ``scaletorch_tpu.inference`` — the serving half
(KV-cache decode engine with continuous batching over the same mesh/TP
specs; see docs/inference.md).
"""

__version__ = "0.1.0"

# Importing the package must not import jax: the pure-AST analysis
# package (jaxlint, run by the dep-less CI lint job), the wire-protocol
# clients and chip_smoke.py's parent process all import it without one.
from scaletorch_tpu import env  # noqa: F401
