"""ST7xx — deep-tier jaxpr/HLO semantic audit of compiled entry points.

The AST tier (ST1xx-ST6xx) reasons about source text; this tier reasons
about what XLA actually lowered. It abstractly traces a manifest of
registered entry points — the SPMD train step, the declarative train
step, the inference prefill/decode steps — on virtual CPU meshes
(``--xla_force_host_platform_device_count``; no TPU, no real compute:
every argument is a ``ShapeDtypeStruct``) and walks the jaxpr and the
compiled HLO to check invariants the AST cannot see:

ST700  entry point failed to build/trace/compile (the audit itself is
       part of the contract — a manifest entry that stops compiling is
       a finding, not a skip)
ST701  wire-dtype mismatch on the quantized axis: the config says the
       dp-edge gradient all-reduce is int8, but the lowered program
       moves large non-int8 payloads over that axis (or no int8
       collective at all) — the silent forfeiture of the 4x wire-byte
       win that PR 5 attested once; this makes it a standing gate
ST702  donation annotations did not survive lowering (no
       input/output aliasing in the compiled module) — on TPU that is
       a whole extra params+opt-state footprint in HBM
ST703  a collective over an axis the schedule expects hoisted (the
       single-flush gradient reduction) appears INSIDE a scan/while
       body — it would fire once per microbatch instead of once per
       step
ST704  a single collective result exceeds the entry's replication cap
       (several times the parameter footprint) — the signature of a
       large intermediate silently replicated across the mesh

Each entry point's builder lives NEXT TO the entry point it audits
(``parallel/spmd.audit_entry``, ``trainer/train_step.audit_entry``,
``inference/decode.audit_entry_paged_prefill``/``_paged_decode``)
and returns a
plain dict — the runtime modules never import the analyzer. This module
imports jax and is only pulled in by the ``--tier deep`` CLI path and
its tests; the pure-AST tier stays jax-free.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Sequence, Tuple

from .core import Finding
from .hlo import parse_collectives

# (entry name, module, builder attr) — the registered deep-audit entry
# points. The name is declared here (and echoed by the builder) so
# --entries can filter BEFORE running any builder.
MANIFEST: Tuple[Tuple[str, str, str], ...] = (
    ("spmd_train_step", "scaletorch_tpu.parallel.spmd", "audit_entry"),
    ("declarative_train_step", "scaletorch_tpu.trainer.train_step",
     "audit_entry"),
    ("paged_prefill_step", "scaletorch_tpu.inference.decode",
     "audit_entry_paged_prefill"),
    ("paged_decode_step", "scaletorch_tpu.inference.decode",
     "audit_entry_paged_decode"),
    ("disagg_prefill_slice", "scaletorch_tpu.inference.disagg",
     "audit_entry_prefill_slice"),
    ("disagg_decode_slice", "scaletorch_tpu.inference.disagg",
     "audit_entry_decode_slice"),
)

# jaxpr primitives that move bytes between mesh members. pvary /
# pbroadcast are type-level VMA ops (no wire) and deliberately absent.
_COLLECTIVE_PRIMS = {
    "psum", "psum2", "psum_invariant", "pmin", "pmax", "all_to_all",
    "all_gather", "all_gather_invariant", "reduce_scatter", "ppermute",
}
_LOOP_PRIMS = {"scan", "while"}

# Payloads at or below this many elements over the quantized axis are
# sidecar traffic (the per-block fp32 scales, scalar loss/metric means)
# and exempt from the ST701 wire-dtype check.
_SMALL_ELEMS = 4096

_WIRE_DTYPE = {"int8": "int8", "bf16": "bfloat16", "fp32": "float32"}


@dataclasses.dataclass(frozen=True)
class JaxprCollective:
    """One collective equation from a traced entry point."""

    prim: str
    axes: Tuple[str, ...]
    dtype: str          # first operand dtype
    elems: int          # max(total operand, total result) elements
    bytes: int          # same, in bytes
    in_loop: bool       # inside a scan/while body


def _aval_stats(vars_) -> Tuple[int, int]:
    elems = 0
    nbytes = 0
    for v in vars_:
        aval = getattr(v, "aval", None)
        if aval is None or not hasattr(aval, "shape"):
            continue
        n = 1
        for d in aval.shape:
            n *= int(d)
        elems += n
        nbytes += n * getattr(aval.dtype, "itemsize", 4)
    return elems, nbytes


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        if hasattr(v, "eqns"):            # raw Jaxpr (shard_map bodies)
            yield v
        elif hasattr(v, "jaxpr"):         # ClosedJaxpr (pjit/scan/remat)
            yield v.jaxpr
        elif isinstance(v, (list, tuple)):  # cond branches etc.
            for b in v:
                if hasattr(b, "eqns"):
                    yield b
                elif hasattr(b, "jaxpr"):
                    yield b.jaxpr


def collect_jaxpr_collectives(jaxpr) -> List[JaxprCollective]:
    """Every collective equation in ``jaxpr``, recursively, with the
    named mesh axes it runs over and whether a scan/while body holds it."""
    out: List[JaxprCollective] = []

    def walk(jx, in_loop: bool) -> None:
        for eqn in jx.eqns:
            prim = eqn.primitive.name
            if prim in _COLLECTIVE_PRIMS:
                axes = eqn.params.get("axes",
                                      eqn.params.get("axis_name", ()))
                if not isinstance(axes, (tuple, list)):
                    axes = (axes,)
                axes = tuple(str(a) for a in axes if a is not None)
                in_e, in_b = _aval_stats(eqn.invars)
                out_e, out_b = _aval_stats(eqn.outvars)
                dtypes = [
                    str(v.aval.dtype) for v in eqn.invars
                    if hasattr(v, "aval") and hasattr(v.aval, "dtype")
                ]
                out.append(JaxprCollective(
                    prim=prim, axes=axes, dtype=dtypes[0] if dtypes else "?",
                    elems=max(in_e, out_e), bytes=max(in_b, out_b),
                    in_loop=in_loop,
                ))
            for sub in _sub_jaxprs(eqn):
                walk(sub, in_loop or prim in _LOOP_PRIMS)

    walk(jaxpr, False)
    return out


# -- entry loading ------------------------------------------------------------

def load_entries(
    names: Optional[Sequence[str]] = None,
) -> Tuple[List[dict], List[Finding]]:
    """Build the manifest's entry dicts; builder failures become ST700
    findings instead of crashing the whole audit."""
    import importlib

    entries: List[dict] = []
    errors: List[Finding] = []
    known = [name for name, _, _ in MANIFEST]
    if names:
        for n in sorted(set(names) - set(known)):
            errors.append(Finding(
                file="scaletorch_tpu/analysis/jaxpr_audit.py", line=1,
                code="ST700", severity="error",
                message=f"unknown audit entry {n!r}; known: {sorted(known)}",
            ))
    for name, mod_name, attr in MANIFEST:
        if names and name not in names:
            continue  # scoped runs never execute unselected builders
        try:
            mod = importlib.import_module(mod_name)
            entry = getattr(mod, attr)()
        except Exception as exc:
            errors.append(Finding(
                file=mod_name.replace(".", "/") + ".py", line=1,
                code="ST700", severity="error",
                message=f"audit entry builder {mod_name}.{attr} failed: "
                        f"{exc!r}",
            ))
            continue
        if entry["name"] != name:
            errors.append(Finding(
                file=mod_name.replace(".", "/") + ".py", line=1,
                code="ST700", severity="error",
                message=(
                    f"audit entry builder {mod_name}.{attr} returned name "
                    f"{entry['name']!r} but the manifest registers it as "
                    f"{name!r}"
                ),
            ))
            continue
        entries.append(entry)
    return entries, errors


# -- the shared compile -------------------------------------------------------

@dataclasses.dataclass
class CompiledEntry:
    """One manifest entry traced and compiled exactly once — the shared
    substrate of the deep tier (ST7xx/ST8xx, this module) and the memory
    tier (ST10xx, analysis/memory.py), so ``--tier deep,memory`` pays a
    single compile per entry."""

    entry: dict
    jaxpr: object          # ClosedJaxpr from the abstract trace
    compiled: object       # jax Compiled (memory_analysis() lives here)
    compiled_text: str     # compiled HLO text


def compile_entry(
    entry: dict,
) -> Tuple[Optional["CompiledEntry"], List[Finding]]:
    """Trace/lower/compile one built entry on the virtual mesh. Failures
    become ST700 findings (the audit itself is part of the contract), in
    which case the CompiledEntry is None."""
    import jax

    name = entry["name"]
    file = entry["file"]
    findings: List[Finding] = []

    ndev = len(jax.devices())
    if ndev < entry.get("min_devices", 1):
        findings.append(Finding(
            file=file, line=1, code="ST700", severity="error",
            message=(
                f"audit entry {name!r} needs >= {entry['min_devices']} "
                f"devices but only {ndev} are visible — run under "
                "JAX_PLATFORMS=cpu with XLA_FLAGS="
                "--xla_force_host_platform_device_count=8 "
                "(the --tier deep CLI sets this up when jax is not yet "
                "initialized)"
            ),
        ))
        return None, findings

    try:
        traced = entry["fn"].trace(*entry["args"])
        jaxpr = traced.jaxpr
        lowered = (traced.lower() if hasattr(traced, "lower")
                   else entry["fn"].lower(*entry["args"]))
        compiled = lowered.compile()
        compiled_text = compiled.as_text()
    except Exception as exc:
        findings.append(Finding(
            file=file, line=1, code="ST700", severity="error",
            message=f"audit entry {name!r} failed to trace/compile: {exc!r}",
        ))
        return None, findings
    return CompiledEntry(
        entry=entry, jaxpr=jaxpr, compiled=compiled,
        compiled_text=compiled_text,
    ), findings


# -- the audit ----------------------------------------------------------------

def audit_compiled(ce: "CompiledEntry") -> Tuple[List[Finding], dict]:
    """The ST7xx checks + comm report over an already-compiled entry."""
    entry = ce.entry
    cols = collect_jaxpr_collectives(ce.jaxpr)
    hlo_cols = parse_collectives(ce.compiled_text)

    findings: List[Finding] = []
    findings.extend(_check_wire_dtype(entry, cols))
    findings.extend(_check_donation(entry, ce.compiled_text))
    findings.extend(_check_hoisting(entry, cols))
    findings.extend(_check_replication(entry, hlo_cols))
    return findings, _comm_report(cols, hlo_cols)


def audit_entry(entry: dict) -> Tuple[List[Finding], Optional[dict]]:
    """(findings, comm report) for one built entry point. The report
    feeds the comm-budget gate (analysis/budget.py) and is None when the
    entry failed to compile."""
    ce, findings = compile_entry(entry)
    if ce is None:
        return findings, None
    fs, report = audit_compiled(ce)
    return findings + fs, report


def audit_all(
    names: Optional[Sequence[str]] = None,
) -> Tuple[List[Finding], Dict[str, dict]]:
    """Audit every manifest entry (or the named subset). Returns the
    findings plus per-entry comm reports for the budget gate."""
    entries, findings = load_entries(names)
    reports: Dict[str, dict] = {}
    for entry in entries:
        fs, report = audit_entry(entry)
        findings.extend(fs)
        if report is not None:
            reports[entry["name"]] = report
    return findings, reports


# -- checks -------------------------------------------------------------------

def _check_wire_dtype(entry: dict, cols: List[JaxprCollective]
                      ) -> List[Finding]:
    qa = entry.get("quantized_axis")
    if not qa:
        return []
    axis, cfg_dtype = qa
    want = _WIRE_DTYPE.get(cfg_dtype, cfg_dtype)
    if want == "float32":
        return []  # nothing quantized to verify
    on_axis = [c for c in cols if axis in c.axes]
    out: List[Finding] = []
    offenders = [
        c for c in on_axis if c.elems > _SMALL_ELEMS and c.dtype != want
    ]
    for c in offenders:
        out.append(Finding(
            file=entry["file"], line=1, code="ST701", severity="error",
            message=(
                f"entry {entry['name']!r}: configured {cfg_dtype} wire on "
                f"axis {axis!r}, but the lowered program runs `{c.prim}` "
                f"over {c.axes} with {c.elems} {c.dtype} elements — the "
                "quantized all-reduce was silently bypassed (wire bytes "
                f"~{4 if want == 'int8' else 2}x over budget on the DCN "
                "edge)"
            ),
        ))
    if not any(c.dtype == want for c in on_axis):
        out.append(Finding(
            file=entry["file"], line=1, code="ST701", severity="error",
            message=(
                f"entry {entry['name']!r}: configured {cfg_dtype} wire on "
                f"axis {axis!r}, but no {want} collective over that axis "
                "was lowered at all — the quantized path is not in the "
                "compiled program"
            ),
        ))
    return out


def _check_donation(entry: dict, compiled_text: str) -> List[Finding]:
    if not entry.get("expect_donation"):
        return []
    # non-empty alias map; whitespace-tolerant so XLA print-format drift
    # across releases doesn't fake a lost donation
    if re.search(r"input_output_alias=\{\s*\{", compiled_text):
        return []
    return [Finding(
        file=entry["file"], line=1, code="ST702", severity="error",
        message=(
            f"entry {entry['name']!r} declares donated arguments but the "
            "compiled module has no input/output aliasing — donation was "
            "lost in lowering (on TPU this doubles the step's persistent "
            "HBM: params/opt-state or KV cache are copied, not updated "
            "in place)"
        ),
    )]


def _check_hoisting(entry: dict, cols: List[JaxprCollective]
                    ) -> List[Finding]:
    hoisted = set(entry.get("hoisted_axes", ()))
    if not hoisted:
        return []
    out: List[Finding] = []
    for c in cols:
        bad = hoisted & set(c.axes)
        if c.in_loop and bad:
            out.append(Finding(
                file=entry["file"], line=1, code="ST703", severity="error",
                message=(
                    f"entry {entry['name']!r}: `{c.prim}` over "
                    f"{sorted(bad)} runs INSIDE a scan/while body — the "
                    "schedule expects this axis reduced once per step "
                    "after accumulation (the no_sync single-flush "
                    "contract), not once per microbatch"
                ),
            ))
    return out


def _check_replication(entry: dict, hlo_cols) -> List[Finding]:
    cap_mb = entry.get("max_collective_result_mb")
    if not cap_mb:
        return []
    out: List[Finding] = []
    for rec in hlo_cols:
        mb = rec.result_bytes / 1e6
        if mb > cap_mb:
            out.append(Finding(
                file=entry["file"], line=1, code="ST704", severity="error",
                message=(
                    f"entry {entry['name']!r}: a `{rec.op}` result is "
                    f"{mb:.2f} MB (> cap {cap_mb:.2f} MB, several times "
                    "the parameter footprint) — a large intermediate is "
                    "being replicated across the mesh instead of staying "
                    "sharded"
                ),
            ))
    return out


# -- comm report (budget backend) ---------------------------------------------

def _comm_report(cols: List[JaxprCollective], hlo_cols) -> dict:
    """Per-named-axis counts/payload (jaxpr view) + per-(op, dtype) wire
    bytes (compiled view) — the two ledgers the comm budget pins."""
    axes: Dict[str, Dict[str, float]] = {}
    for c in cols:
        key = ",".join(sorted(c.axes)) or "<unnamed>"
        slot = axes.setdefault(key, {"count": 0, "payload_mb": 0.0})
        slot["count"] += 1
        slot["payload_mb"] += c.bytes / 1e6
    hlo: Dict[str, Dict[str, float]] = {}
    total = 0.0
    for rec in hlo_cols:
        key = f"{rec.op}:{rec.dtype}"
        slot = hlo.setdefault(key, {"count": 0, "wire_mb": 0.0})
        slot["count"] += 1
        slot["wire_mb"] += rec.wire_bytes / 1e6
        total += rec.wire_bytes / 1e6
    for slot in axes.values():
        slot["payload_mb"] = round(slot["payload_mb"], 4)
    for slot in hlo.values():
        slot["wire_mb"] = round(slot["wire_mb"], 4)
    return {"axes": axes, "hlo": hlo, "total_wire_mb": round(total, 4)}
