"""Centralised environment-variable registry.

TPU-native counterpart of the reference's ``scaletorch/env.py:8-29``: a single
place that declares every runtime toggle the framework reads, with defaults,
so models/comms never reach for ``os.environ`` ad hoc.
"""

from __future__ import annotations

import os
from typing import Any, Callable

_REGISTRY: dict[str, tuple[str, Callable[[str], Any]]] = {}


def _as_bool(v: str) -> bool:
    return v.lower() in ("1", "true", "yes", "on")


def _as_optional_int(v: str) -> int | None:
    return int(v) if v else None


def register_env(name: str, default: str, parser: Callable[[str], Any] = str) -> None:
    """Declare an environment variable the framework reads."""
    _REGISTRY[name] = (default, parser)


def get_env(name: str) -> Any:
    """Read a registered environment variable, applying default + parser."""
    if name not in _REGISTRY:
        raise KeyError(f"env var {name!r} is not registered; call register_env first")
    default, parser = _REGISTRY[name]
    return parser(os.environ.get(name, default))


def env_snapshot() -> dict[str, Any]:
    """Current values of every registered env var (for logging/diagnostics)."""
    return {k: get_env(k) for k in sorted(_REGISTRY)}


def env_override(name: str, fallback: Any) -> Any:
    """Registered env var when PRESENT — including an explicit 0/empty, so
    a restarted job can CANCEL a config-armed knob without a config edit —
    else the caller's fallback (usually the config field). The single home
    of the present-wins contract shared by every SCALETORCH_TPU_FT_*
    consumer (resilience.FaultInjector, resilience_distributed)."""
    if os.environ.get(name) is not None:
        return get_env(name)
    return fallback


# ---- process-rank discovery (shared by dist.py and logger.py) ---------------
# The first three are the explicit 'env' launcher contract
# (dist.init_distributed); the scheduler-set tail is only a pre-backend-init
# fallback for log gating (logger._process_index_noinit).
ENV_LAUNCHER_RANK_VARS: tuple[str, ...] = ("JAX_PROCESS_ID", "PROCESS_ID", "RANK")
RANK_DISCOVERY_VARS: tuple[str, ...] = ENV_LAUNCHER_RANK_VARS + (
    "SLURM_PROCID",
    "OMPI_COMM_WORLD_RANK",
)

# ---- core toggles (parity with reference scaletorch/env.py) -----------------
register_env("FLASH_ATTEN", "1", _as_bool)          # use pallas flash attention
register_env("CONTEXT_PARALLEL", "0", _as_bool)     # ring attention enabled
register_env("SEQUENCE_PARALLEL", "0", _as_bool)    # Megatron-style SP on tp axis
register_env("VERBOSE", "0", _as_bool)              # chatty comms logging
register_env("DTYPE", "bfloat16", str)              # compute dtype
# TPU-specific additions
register_env("SCALETORCH_TPU_MATMUL_PRECISION", "", str)
register_env("SCALETORCH_TPU_DISABLE_PALLAS", "0", _as_bool)  # force XLA fallbacks
# AOT compile-only sessions (tools/aot_memory.py) target a TPU topology
# with no TPU attached, so the platform test below cannot see it: they
# set this to lower the Pallas kernels anyway. Nothing that EXECUTES
# sets it — on a device the platform is the test.
register_env("SCALETORCH_TPU_FORCE_PALLAS", "0", _as_bool)
# Context-parallel sequence layout: 'contiguous' or 'zigzag' (balanced
# causal work per ring rank; needs the loader's zigzag token order —
# parallel/zigzag.py). Read by the 'ring' backend at trace time.
register_env("SCALETORCH_TPU_CP_LAYOUT", "contiguous", str)
# Sequence-chunk length for the fused LM-head + cross-entropy (bounds the
# live fp32 [B, C, V/tp] logits transient; halve on HBM-edge configs).
register_env("SCALETORCH_TPU_CE_CHUNK", "1024", int)
# Grouped-MLP Pallas kernel for MoE expert compute (ops/pallas/
# grouped_mlp.py): skips capacity slots past each expert's fill count.
# Default OFF until measured faster than the batched einsum on real
# chips (the einsum is already MXU-dense; the win is the padding skip).
register_env("SCALETORCH_TPU_GROUPED_MLP_KERNEL", "0", _as_bool)
# Flash-kernel tile sizes (ops/pallas/flash.py). Unset (the default):
# each of the three kernels takes its (bq, bkv) from the call's shapes
# (flash.flash_blocks, a sweep on the v5e: PERF.md, PR 62). Set by hand,
# a value overrides the rule for ALL THREE kernels, halved until it
# divides the sequence: how tools/optimize_mfu.py --flash-blocks reads a
# whole step at one uniform pair. An override, not a tuning surface:
# deleting the pair is ROADMAP D5's.
register_env("SCALETORCH_TPU_FLASH_BLOCK_Q", "", _as_optional_int)
register_env("SCALETORCH_TPU_FLASH_BLOCK_KV", "", _as_optional_int)
# Paged-decode attention (ops/pallas/paged_attention.py): 1 (default)
# lets single-token decode on a TPU backend take the Pallas kernel; 0
# forces the lax gather fallback everywhere (the bit-parity oracle).
register_env("SCALETORCH_TPU_PAGED_KERNEL", "1", _as_bool)

# Fault-injection hooks (resilience.FaultInjector): 0 = off. Env overrides
# the ft_* config fields so a running job can be drilled without a config
# edit (e.g. SCALETORCH_TPU_FT_SIGTERM_STEP=100 simulates preemption).
register_env("SCALETORCH_TPU_FT_NAN_STEP", "0", int)
register_env("SCALETORCH_TPU_FT_FAIL_SAVES", "0", int)
register_env("SCALETORCH_TPU_FT_SIGTERM_STEP", "0", int)
# Telemetry drill: stall one optimizer step at the boundary so the
# slow-step detector (telemetry/profiling.py) arms a profiler window.
register_env("SCALETORCH_TPU_FT_SLOW_STEP_STEP", "0", int)
register_env("SCALETORCH_TPU_FT_SLOW_STEP_SECONDS", "0.5", float)
# Multi-host resilience (resilience_distributed.py): restrict the SIGTERM
# drill to one host, inject a step-boundary stall, corrupt one data-stream
# read, tune the hang watchdog, and toggle cross-host decision
# coordination without a config edit.
register_env("SCALETORCH_TPU_FT_SIGTERM_HOST", "-1", int)
register_env("SCALETORCH_TPU_FT_HANG_STEP", "0", int)
register_env("SCALETORCH_TPU_FT_BAD_BATCH_STEP", "0", int)
register_env("SCALETORCH_TPU_FT_HANG_TIMEOUT", "0", float)
register_env("SCALETORCH_TPU_FT_COORDINATE", "1", _as_bool)
# Elastic drills (resilience_distributed.ElasticCoordinator): hard-kill
# one host after step k (survivors remesh and continue), or stall one
# host past the elastic epoch-bus deadline (the fleet evicts it and it
# must park-and-rejoin). KILL_HOST selects the target rank for both.
register_env("SCALETORCH_TPU_FT_KILL_HOST_STEP", "0", int)
register_env("SCALETORCH_TPU_FT_KILL_HOST", "-1", int)
register_env("SCALETORCH_TPU_FT_HOST_HANG_ELASTIC", "0", int)
# Serving fault injection (inference/resilience.ServingFaultInjector):
# same present-wins contract over the ft_serve_* config fields; steps are
# 1-based decode steps of the engine's lifetime.
register_env("SCALETORCH_TPU_FT_SERVE_NAN_STEP", "0", int)
register_env("SCALETORCH_TPU_FT_SERVE_NAN_SLOT", "0", int)
register_env("SCALETORCH_TPU_FT_SERVE_SLOW_STEP", "0", int)
register_env("SCALETORCH_TPU_FT_SERVE_SLOW_SECONDS", "30", float)
register_env("SCALETORCH_TPU_FT_SERVE_SUBMIT_STORM_STEP", "0", int)
register_env("SCALETORCH_TPU_FT_SERVE_SUBMIT_STORM_COUNT", "8", int)
register_env("SCALETORCH_TPU_FT_SERVE_DEADLINE_STORM_STEP", "0", int)
# Gateway fault injection (serving/gateway.py, same present-wins contract
# over the ft_gw_* config fields; the counting unit is 1-based HTTP
# requests — tenant storm at arrival k, replica-down at dispatch k).
register_env("SCALETORCH_TPU_FT_GW_TENANT_STORM_AT", "0", int)
register_env("SCALETORCH_TPU_FT_GW_TENANT_STORM_COUNT", "8", int)
register_env("SCALETORCH_TPU_FT_GW_REPLICA_DOWN_AT", "0", int)
register_env("SCALETORCH_TPU_FT_GW_REPLICA_CRASH_AT", "0", int)
register_env("SCALETORCH_TPU_FT_GW_REPLICA_HANG_AT", "0", int)
# Warm-rejoin drills (serving/remote.py donor side; the counting unit is
# 1-based warm-transfer chunks on the /warm stream).
register_env("SCALETORCH_TPU_FT_GW_WARM_DONOR_CRASH_AT", "0", int)
register_env("SCALETORCH_TPU_FT_GW_WARM_CORRUPT_CHUNK_AT", "0", int)
# Telemetry (scaletorch_tpu/telemetry/): present-wins over the config
# fields (an explicitly EMPTY dir cancels a config-armed telemetry run).
register_env("SCALETORCH_TPU_TELEMETRY_DIR", "", str)
register_env("SCALETORCH_TPU_PROFILE_STEPS", "", str)


# ---- persistent compilation cache -------------------------------------------
# In-checkout default, git-ignored. The directory is part of every cache
# key's lookup, so it is a FIXED path: a temp dir, a pid or a timestamp in
# it would make every process miss.
COMPILE_CACHE_DEFAULT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def configure_compile_cache() -> str:
    """Point jax's persistent compilation cache somewhere a later process
    finds again; every entry point calls this first. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and nothing
    is set in code; otherwise the cache lives in ``COMPILE_CACHE_DEFAULT``.
    Returns the directory in use."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DEFAULT)
    return COMPILE_CACHE_DEFAULT


def compile_cache_dir() -> "str | None":
    """The directory jax's persistent compile cache is kept in by now
    (``configure_compile_cache`` or ``JAX_COMPILATION_CACHE_DIR``); None
    where no process has asked for one (the tests). What is kept BESIDE
    the compiled programs (``inference/decode.store_orders``) asks
    here."""
    import jax

    return jax.config.jax_compilation_cache_dir


# ---- one chip of a multi-chip host ------------------------------------------
def one_chip_env(chip: int = 0) -> dict[str, str]:
    """Environment that shows a NEW process one chip of a multi-chip TPU
    host (libtpu reads it at start-up; it changes nothing in a process
    that already initialised jax). A chip belongs to one process, and a
    Trainer's mesh spans every device its process sees — so a one-chip
    run on a four-chip host is a process started with this."""
    return {
        "TPU_VISIBLE_CHIPS": str(chip),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
    }
