"""Multi-host runtime bootstrap — the TPU-native ``init_dist``.

Counterpart of reference ``scaletorch/dist/utils.py:78-251`` (``init_dist``
+ per-launcher discovery ``_init_dist_pytorch`` / ``_init_dist_slurm`` /
``_init_dist_mpi``). The torch stack must build NCCL/HCCL process groups
per parallel axis; on TPU all of that collapses into ONE call —
``jax.distributed.initialize`` — after which ``jax.devices()`` spans every
host and the existing mesh/``shard_map`` code is multi-host for free (XLA
routes collectives over ICI within a slice and DCN across slices).

What this module keeps from the reference is the *launcher discovery*
contract (``infer_launcher``, dist/utils.py:144-152): the same process can
be started by torchrun-style env vars, SLURM, or MPI, and finds its
coordinator/rank without code changes. JAX's own cluster detection covers
SLURM/OMPI/TPU-metadata natively; the env launcher additionally accepts
torchrun names (MASTER_ADDR/MASTER_PORT/RANK/WORLD_SIZE) so reference
launch scripts port 1:1.

Data feeding under multi-host SPMD: every process holds the *global* host
batch (deterministic loaders make this free) and ``put_global`` materialises
a global jax.Array by handing each process only its addressable shards —
the role of the reference's per-rank sampler slicing (dataloader.py:170-233).
"""

from __future__ import annotations

import os
from typing import Any, Optional, Sequence

import jax
import numpy as np

# Env names: JAX-native first, torchrun-style fallback (reference
# _init_dist_pytorch reads RANK/WORLD_SIZE/MASTER_*, dist/utils.py:152-165).
from scaletorch_tpu.env import ENV_LAUNCHER_RANK_VARS as _PID_VARS
from scaletorch_tpu.utils.logger import get_logger

_initialized = False

_COORD_VARS = ("JAX_COORDINATOR_ADDRESS", "COORDINATOR_ADDRESS")
_NPROC_VARS = ("JAX_NUM_PROCESSES", "NUM_PROCESSES", "WORLD_SIZE")


def _first_env(names: Sequence[str]) -> Optional[str]:
    for n in names:
        v = os.environ.get(n)
        if v not in (None, ""):
            return v
    return None


def infer_launcher() -> str:
    """Detect how this process was started (reference dist/utils.py:144-152).

    Returns one of 'env' (explicit coordinator env vars, incl. torchrun
    style), 'slurm', 'mpi', or 'none' (single process).
    """
    # 'env' requires a coordinator address: a bare WORLD_SIZE (stale
    # torchrun/SageMaker ambience) must NOT flip a single-process run into
    # a hard "missing coordinator" error.
    if _first_env(_COORD_VARS) or os.environ.get("MASTER_ADDR"):
        return "env"
    if "SLURM_NTASKS" in os.environ and int(os.environ["SLURM_NTASKS"]) > 1:
        return "slurm"
    if "OMPI_COMM_WORLD_SIZE" in os.environ:
        return "mpi"
    return "none"


def _env_coordinator() -> Optional[str]:
    addr = _first_env(_COORD_VARS)
    if addr:
        return addr
    host = os.environ.get("MASTER_ADDR")
    if host:
        port = os.environ.get("MASTER_PORT", "29500")
        return f"{host}:{port}"
    return None


def init_distributed(
    launcher: str = "auto",
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    local_device_ids: Optional[Sequence[int]] = None,
) -> bool:
    """Initialise the multi-process JAX runtime. Idempotent.

    Returns True when a multi-process runtime is (now) active, False for
    single-process. ``launcher='auto'`` infers from the environment; a
    single-process start is never an error (reference init_dist raises on
    unknown launchers — here 'none' is the benign default because SPMD
    code is identical either way).
    """
    global _initialized
    if _initialized:
        return jax.process_count() > 1
    # Detect an externally-initialised runtime WITHOUT touching the XLA
    # backend (jax.process_count() would initialise it and make a
    # subsequent distributed.initialize impossible).
    if jax.distributed.is_initialized():
        _initialized = True
        return jax.process_count() > 1

    if launcher == "auto":
        launcher = infer_launcher()
    if launcher == "none":
        return False
    if launcher not in ("env", "slurm", "mpi"):
        raise ValueError(
            f"launcher must be auto|env|slurm|mpi|none, got {launcher!r}"
        )

    # CPU backend (tests / virtual meshes) needs explicit cross-process
    # collectives; gloo is the portable choice. Must be set before backend
    # init. Harmless no-op for the TPU backend, which ignores it.
    if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
        jax.config.update("jax_cpu_collectives_implementation", "gloo")

    kwargs: dict[str, Any] = {}
    if launcher == "env":
        addr = coordinator_address or _env_coordinator()
        nproc = num_processes if num_processes is not None else _first_env(_NPROC_VARS)
        pid = process_id if process_id is not None else _first_env(_PID_VARS)
        if addr is None or nproc is None or pid is None:
            raise ValueError(
                "env launcher needs coordinator_address, num_processes and "
                "process_id (flags, or JAX_COORDINATOR_ADDRESS/"
                "JAX_NUM_PROCESSES/JAX_PROCESS_ID, or torchrun-style "
                "MASTER_ADDR[:MASTER_PORT]/WORLD_SIZE/RANK)"
            )
        kwargs = dict(
            coordinator_address=addr,
            num_processes=int(nproc),
            process_id=int(pid),
        )
        if local_device_ids is not None:
            kwargs["local_device_ids"] = list(local_device_ids)
    # slurm/mpi: jax's ClusterEnv auto-detection (SlurmCluster/OmpiCluster)
    # resolves coordinator + ranks from the scheduler env — the role of
    # reference _init_dist_slurm's scontrol scraping (dist/utils.py:206-251).
    jax.distributed.initialize(**kwargs)
    _initialized = True
    get_logger().info(
        f"distributed runtime up: launcher={launcher} "
        f"process {jax.process_index()}/{jax.process_count()} "
        f"local_devices={jax.local_device_count()} "
        f"global_devices={jax.device_count()}"
    )
    return True


def shutdown_distributed() -> None:
    """Tear down the coordinator link (reference cleanup_dist)."""
    global _initialized
    if _initialized:
        jax.distributed.shutdown()
        _initialized = False


def is_distributed() -> bool:
    return jax.process_count() > 1


def process_index() -> int:
    return jax.process_index()


def process_count() -> int:
    return jax.process_count()


def is_main_process() -> bool:
    """Reference ``is_main_process``/rank-0 gating (dist/utils.py role)."""
    return jax.process_index() == 0


def barrier(name: str = "barrier") -> None:
    """Block until every process reaches this point (reference
    torch_dist.barrier role). No-op single-process."""
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(name)


# ---------------------------------------------------------------------------
# Cross-process OBJECT collectives (reference dist/object_ops.py:26-318 +
# gather_utils.py:24-211). Under single-controller SPMD most result
# collection is moot — every process computes the same globals — but eval
# loops that shard WORK across processes (per-process files, per-host
# generation samples) still need to move arbitrary picklables. The wire
# is pickled bytes -> padded uint8 arrays -> one device all-gather
# (jax.experimental.multihost_utils), the exact role of the reference's
# _object_to_tensor + all_gather (object_ops.py:26-44).
# ---------------------------------------------------------------------------


def _obj_to_u8(obj: Any) -> np.ndarray:
    import pickle

    return np.frombuffer(pickle.dumps(obj), dtype=np.uint8)


def _u8_to_obj(buf: np.ndarray, size: int) -> Any:
    import pickle

    return pickle.loads(bytes(np.asarray(buf[:size], dtype=np.uint8)))


def all_gather_object(obj: Any) -> list:
    """Every process contributes one picklable; every process receives
    ``[obj_0, ..., obj_{P-1}]`` in process order (reference
    all_gather_object, object_ops.py:186-253)."""
    if jax.process_count() == 1:
        return [obj]
    from jax.experimental import multihost_utils

    buf = _obj_to_u8(obj)
    sizes = np.asarray(
        multihost_utils.process_allgather(np.int64(buf.size)))
    cap = int(sizes.max())
    padded = np.zeros(cap, np.uint8)
    padded[: buf.size] = buf
    bufs = np.asarray(multihost_utils.process_allgather(padded))
    return [_u8_to_obj(bufs[p], int(sizes[p]))
            for p in range(jax.process_count())]


def gather_object(obj: Any, dst: int = 0) -> Optional[list]:
    """Gather picklables to process ``dst``; other processes return None
    (reference gather_object, object_ops.py:256-318). The transport is an
    all-gather (XLA collectives have no rooted object gather); only the
    RESULT visibility is rooted, keeping the reference API."""
    out = all_gather_object(obj)
    return out if jax.process_index() == dst else None


def broadcast_object_list(objs: list, src: int = 0) -> list:
    """Replace every element with ``src``'s version (reference
    broadcast_object_list, object_ops.py:117-183)."""
    if jax.process_count() == 1:
        return list(objs)
    # only src's payload matters: non-src processes contribute a tiny
    # placeholder so the padded all-gather moves src's bytes once, not
    # every process's full copy
    mine = list(objs) if jax.process_index() == src else None
    gathered = all_gather_object(mine)
    chosen = gathered[src]
    if len(chosen) != len(objs):
        raise ValueError(
            f"broadcast_object_list: src={src} holds {len(chosen)} objects, "
            f"this process expected {len(objs)}"
        )
    objs[:] = chosen
    return objs


def collect_results(results: list, size: int,
                    device: str = "cpu") -> Optional[list]:
    """Collect per-process result lists to process 0, round-robin
    interleaved and truncated to ``size`` (reference collect_results,
    gather_utils.py:24-211: rank r holds samples r, r+P, r+2P, ... of a
    round-robin sharded eval set). Non-zero processes return None.

    ``device`` is accepted for reference CLI parity; on TPU there is one
    transport (the uint8 all-gather above), so the value is ignored.
    """
    del device  # single transport on TPU
    parts = all_gather_object(list(results))
    if jax.process_index() != 0:
        return None
    interleaved: list = []
    longest = max((len(p) for p in parts), default=0)
    for j in range(longest):
        for p in parts:
            if j < len(p):
                interleaved.append(p[j])
    return interleaved[:size]


def put_global(host_array, sharding) -> jax.Array:
    """Materialise a global array from an identical host copy per process.

    Single-process this is a plain ``device_put``; multi-process each
    process contributes only the shards on its addressable devices
    (``jax.make_array_from_callback`` slices the host copy per device) —
    the multi-host feeding path the reference implements with per-rank
    sampler offsets (dataloader.py:170-233).
    """
    if isinstance(host_array, jax.Array) and not host_array.is_fully_addressable:
        # Already a global multi-process array (e.g. from the streamed HF
        # loader): fetching it to host would crash — and defeat the point.
        if host_array.sharding == sharding:
            return host_array
        return jax.device_put(host_array, sharding)
    if jax.process_count() == 1:
        return jax.device_put(host_array, sharding)
    host_array = np.asarray(host_array)
    return jax.make_array_from_callback(
        host_array.shape, sharding, lambda idx: host_array[idx]
    )
