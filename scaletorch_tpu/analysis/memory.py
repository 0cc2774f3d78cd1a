"""ST10xx — static HBM accounting and the standing peak-memory budget.

PR 5's one-time HLO wire-byte attestation became PR 6's standing
``comm_budget.json`` gate; this module is the same move for the other
scarce resource. Every deep-tier manifest entry (the SPMD train step,
the declarative quantized-DP step, prefill/decode/paged-decode) is
compiled on the virtual CPU mesh and its memory accounting — argument /
temp / output / alias bytes, from ``compiled.memory_analysis()`` when
the backend provides it, else from a jaxpr buffer-liveness estimator —
is checked against ``tools/hbm_budget.json`` with the same slack /
re-baseline / jax-version-downgrade semantics as the comm budget:

ST1001  peak/temp/argument bytes over budget (or budgeted donation
        alias savings lost, or no budget row at all) — the refactor
        that silently costs HBM
ST1002  donation ineffective: the entry declares donated arguments but
        the compiled module's input/output alias savings don't cover
        their bytes — the runtime twin of ST702 (which only asks
        whether ANY alias survived)
ST1003  precision leak: large fp32 buffers lowered in a bf16-configured
        entry outside the allowlisted accumulation set (softmax, loss,
        optimizer moments, quantization scales)
ST1004  remat violation: a configured checkpoint policy whose scan-body
        residuals still survive to the backward at full-activation
        scale
ST1005  pool-sizing mismatch: the engine's ``kv_cache_bytes`` for the
        audited page pool disagrees with the compiled pool buffer
        bytes — admission math and XLA must share one source of truth

The XLA numbers are exact compiled facts (buffer assignment, donation
aliasing, fusion all applied); the liveness estimator is a linear walk
of the jaxpr that ignores fusion and donation reuse, so it OVERSTATES
peaks — it exists so the tier still runs (and still attributes the
top-k live allocations to source lines via eqn provenance) on backends
whose ``memory_analysis()`` reports nothing. A budget row records which
source produced it; comparing across sources downgrades to a warning,
like jax-version drift.

Like ST7xx/ST8xx, the per-entry contract fields (``donated_min_mb``,
``compute_dtype``, ``kv_cache``, …) are pinned in the builders next to
the entry points — a config mutation fails the gate loudly instead of
relaxing it. This module imports jax lazily and is only pulled in by
the ``--tier memory`` CLI path and its tests.
"""

from __future__ import annotations

import dataclasses
import json
import re
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from .core import Finding
from .jaxpr_audit import _sub_jaxprs

DEFAULT_HBM_BUDGET = Path("tools") / "hbm_budget.json"
# Same growth tolerance story as the comm budget: float noise plus
# benign buffer-assignment drift across compiles.
DEFAULT_TOLERANCE_PCT = 10.0
# Absolute slack in MB: entries whose budget rounds to ~0 must not fail
# on a few KB of scheduling noise.
_ABS_SLACK_MB = 0.25

_BUDGET_FILE = "tools/hbm_budget.json"  # finding location
_TOP_K = 8

# Function-name substrings (matched over the eqn's user stack frames)
# whose fp32 intermediates are legitimate in a bf16 entry: numerically
# fragile accumulations the mixed-precision recipe deliberately keeps
# wide. Entries can extend this via the ``fp32_allow`` contract field.
_FP32_ALLOW = (
    "softmax", "loss", "cross_entropy", "entropy", "logsumexp",
    "norm", "moment", "adam", "lamb", "adafactor", "optimizer",
    "scale", "quant", "rope", "rotary",
)


@dataclasses.dataclass(frozen=True)
class TopAllocation:
    """One live buffer at the estimated peak, attributed to source."""

    nbytes: int
    shape: Tuple[int, ...]
    dtype: str
    site: str       # "file:line (function)" from eqn provenance


@dataclasses.dataclass(frozen=True)
class MemoryAccounting:
    """Per-entry memory ledger. ``peak_bytes`` follows tools/aot_memory's
    formula — arguments + temps + generated code (outputs alias temps or
    arguments in XLA's accounting; summing them double-counts)."""

    argument_bytes: int
    output_bytes: int
    temp_bytes: int
    alias_bytes: int
    generated_code_bytes: int
    peak_bytes: int
    source: str     # "xla" | "jaxpr-liveness"


# ---- XLA accounting ---------------------------------------------------------

def accounting_from_compiled(compiled) -> Optional[MemoryAccounting]:
    """``compiled.memory_analysis()`` as a :class:`MemoryAccounting`, or
    None when the backend provides nothing usable (the caller then falls
    back to the jaxpr liveness estimator)."""
    try:
        m = compiled.memory_analysis()
    except Exception:
        return None
    if m is None:
        return None
    try:
        arg = int(m.argument_size_in_bytes)
        temp = int(m.temp_size_in_bytes)
        out = int(m.output_size_in_bytes)
        alias = int(m.alias_size_in_bytes)
        code = int(m.generated_code_size_in_bytes)
    except (AttributeError, TypeError):
        return None
    if arg == 0 and temp == 0 and out == 0:
        return None  # a backend that stubs the stats out
    return MemoryAccounting(
        argument_bytes=arg, output_bytes=out, temp_bytes=temp,
        alias_bytes=alias, generated_code_bytes=code,
        peak_bytes=arg + temp + code, source="xla",
    )


# ---- jaxpr buffer-liveness estimator ----------------------------------------

def _var_nbytes(v) -> int:
    aval = getattr(v, "aval", None)
    if aval is None or not hasattr(aval, "shape"):
        return 0
    n = 1
    for d in aval.shape:
        n *= int(d)
    return n * getattr(getattr(aval, "dtype", None), "itemsize", 4)


def _var_shape_dtype(v) -> Tuple[Tuple[int, ...], str]:
    aval = getattr(v, "aval", None)
    if aval is None or not hasattr(aval, "shape"):
        return (), "?"
    return tuple(int(d) for d in aval.shape), str(getattr(aval, "dtype", "?"))


def _eqn_site(eqn) -> str:
    """``file:line (function)`` of the closest user frame, for the top-k
    attribution and the ST1003 message."""
    # jax 0.9.0 has no public accessor for an equation's provenance; its
    # private one takes the traceback (None-tolerant). No try/except: a
    # jax that moves it again should fail here, not print "<unknown>".
    from jax._src import source_info_util

    frame = source_info_util.user_frame(eqn.source_info.traceback)
    if frame is None:
        return "<unknown>"
    return f"{frame.file_name}:{frame.start_line} ({frame.function_name})"


def _eqn_frame_names(eqn) -> List[str]:
    from jax._src import source_info_util

    return [f.function_name for f in
            source_info_util.user_frames(eqn.source_info.traceback)]


def _is_literal(v) -> bool:
    # core.Literal carries its value inline; only Vars have liveness
    return hasattr(v, "val")


def _estimate(jx) -> Tuple[int, int, List[TopAllocation]]:
    """One jaxpr level: ``(peak_bytes, input_bytes, top_live_at_peak)``.

    A linear walk in program order: inputs live from the start, each
    equation's outputs allocate, every buffer frees after its last use.
    Sub-jaxprs (pjit/scan/remat bodies, cond branches) contribute their
    own peak *minus* their inputs (already live at the call site) while
    their equation executes. Scan residual stacking is captured by the
    scan equation's ys outvars at this level. No fusion, no donation
    reuse — a deliberate overestimate (see module docstring).
    """
    jx = getattr(jx, "jaxpr", jx)   # ClosedJaxpr also has .eqns — unwrap
    invs = list(getattr(jx, "constvars", ())) + list(jx.invars)
    live: Dict[int, TopAllocation] = {}
    for v in invs:
        shape, dtype = _var_shape_dtype(v)
        live[id(v)] = TopAllocation(
            nbytes=_var_nbytes(v), shape=shape, dtype=dtype,
            site="<argument>",
        )

    last_use: Dict[int, int] = {}
    for i, eqn in enumerate(jx.eqns):
        for v in eqn.invars:
            if not _is_literal(v):
                last_use[id(v)] = i
    n_eqns = len(jx.eqns)
    for v in jx.outvars:
        if not _is_literal(v):
            last_use[id(v)] = n_eqns     # outputs are never freed

    input_bytes = sum(a.nbytes for a in live.values())
    live_bytes = input_bytes
    peak = live_bytes
    top = sorted(live.values(), key=lambda a: -a.nbytes)[:_TOP_K]

    for i, eqn in enumerate(jx.eqns):
        inner_temp = 0
        for sub in _sub_jaxprs(eqn):
            sp, sa, _ = _estimate(sub)
            inner_temp = max(inner_temp, max(0, sp - sa))
        site = _eqn_site(eqn)
        out_allocs = []
        for v in eqn.outvars:
            shape, dtype = _var_shape_dtype(v)
            out_allocs.append(TopAllocation(
                nbytes=_var_nbytes(v), shape=shape, dtype=dtype, site=site,
            ))
        out_bytes = sum(a.nbytes for a in out_allocs)
        cand = live_bytes + inner_temp + out_bytes
        if cand > peak:
            peak = cand
            snapshot = list(live.values()) + out_allocs
            if inner_temp:
                snapshot.append(TopAllocation(
                    nbytes=inner_temp, shape=(), dtype="<body temps>",
                    site=site,
                ))
            top = sorted(snapshot, key=lambda a: -a.nbytes)[:_TOP_K]
        for v, alloc in zip(eqn.outvars, out_allocs):
            live[id(v)] = alloc
        live_bytes += out_bytes
        # free everything whose last use was this equation (including
        # never-used outputs — DropVars die immediately)
        for v in list(eqn.invars) + list(eqn.outvars):
            if _is_literal(v):
                continue
            if last_use.get(id(v), i) <= i and id(v) in live:
                live_bytes -= live.pop(id(v)).nbytes
    return peak, input_bytes, top


def estimate_jaxpr_memory(
    jaxpr,
) -> Tuple[MemoryAccounting, List[TopAllocation]]:
    """Buffer-liveness estimate over a (Closed)Jaxpr — the
    always-available fallback accounting, plus the top-k live
    allocations at the estimated peak for source attribution."""
    jx = getattr(jaxpr, "jaxpr", jaxpr)
    peak, input_bytes, top = _estimate(jx)
    output_bytes = sum(_var_nbytes(v) for v in jx.outvars)
    return MemoryAccounting(
        argument_bytes=input_bytes, output_bytes=output_bytes,
        temp_bytes=max(0, peak - input_bytes), alias_bytes=0,
        generated_code_bytes=0, peak_bytes=peak, source="jaxpr-liveness",
    ), top


def entry_accounting(ce) -> Tuple[MemoryAccounting, List[TopAllocation]]:
    """Accounting for one :class:`~.jaxpr_audit.CompiledEntry` — XLA's
    stats when the backend reports them, the liveness estimate
    otherwise. The top-k attribution always comes from the jaxpr walk
    (XLA's stats carry no per-buffer provenance)."""
    est, top = estimate_jaxpr_memory(ce.jaxpr)
    return accounting_from_compiled(ce.compiled) or est, top


# ---- contract checks (ST1002-ST1005) ----------------------------------------

def _alias_bytes_from_hlo(compiled_text: str, entry: dict) -> int:
    """Fallback alias accounting when ``memory_analysis()`` is absent:
    sum the flattened argument avals named by the compiled module's
    ``input_output_alias`` map."""
    import jax

    from scaletorch_tpu.inference.kv_cache import cache_nbytes

    header = next(
        (ln for ln in compiled_text.splitlines()
         if "input_output_alias=" in ln), "",
    )
    flat = jax.tree_util.tree_leaves(entry["args"])
    total = 0
    for m in re.finditer(r"\((\d+),\s*\{\}", header):
        idx = int(m.group(1))
        if idx < len(flat):
            total += cache_nbytes(flat[idx])
    return total


def _check_donation_bytes(
    entry: dict, acct: MemoryAccounting, compiled_text: str
) -> List[Finding]:
    want_mb = entry.get("donated_min_mb")
    if not entry.get("expect_donation") or not want_mb:
        return []
    if acct.source == "xla":
        alias_mb = acct.alias_bytes / 1e6
    else:
        alias_mb = _alias_bytes_from_hlo(compiled_text, entry) / 1e6
    if alias_mb >= want_mb:
        return []
    return [Finding(
        file=entry["file"], line=1, code="ST1002", severity="error",
        message=(
            f"entry {entry['name']!r}: declared donated arguments should "
            f"alias >= {want_mb:.4f} MB of outputs but the compiled "
            f"module only aliases {alias_mb:.4f} MB — donation is "
            "ineffective (on TPU the un-aliased bytes are a second copy "
            "of params/opt-state or KV cache held across the step)"
        ),
    )]


def _iter_eqns(jx):
    for eqn in jx.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from _iter_eqns(sub)


def _check_precision(entry: dict, jaxpr) -> List[Finding]:
    contract = str(entry.get("compute_dtype") or "")
    if contract not in ("bf16", "bfloat16"):
        return []
    min_elems = int(entry.get("fp32_large_elems", 1 << 20))
    allow = _FP32_ALLOW + tuple(entry.get("fp32_allow", ()))
    jx = getattr(jaxpr, "jaxpr", jaxpr)
    by_site: Dict[str, Tuple[int, int]] = {}   # site -> (count, max bytes)
    for eqn in _iter_eqns(jx):
        for v in eqn.outvars:
            shape, dtype = _var_shape_dtype(v)
            if dtype != "float32":
                continue
            elems = 1
            for d in shape:
                elems *= d
            if elems < min_elems:
                continue
            # frame names are qualified ("f.<locals>.<lambda>"); the
            # synthetic components ("<lambda>", "<locals>", "<module>")
            # carry no semantic name — they must not satisfy the
            # allowlist ("lamb" would match every "<lambda>")
            frames = [part.lower()
                      for name in _eqn_frame_names(eqn)
                      for part in name.split(".")
                      if not part.startswith("<")]
            if any(a in f for f in frames for a in allow):
                continue
            site = _eqn_site(eqn)
            n, mx = by_site.get(site, (0, 0))
            by_site[site] = (n + 1, max(mx, _var_nbytes(v)))
    out: List[Finding] = []
    for site, (n, mx) in sorted(by_site.items()):
        out.append(Finding(
            file=entry["file"], line=1, code="ST1003", severity="error",
            message=(
                f"entry {entry['name']!r} is configured bf16 but lowers "
                f"{n} large fp32 buffer(s) (up to {mx / 1e6:.4f} MB, >= "
                f"{min_elems} elements) at {site} — outside the "
                "allowlisted accumulation set (softmax/loss/optimizer "
                "moments/quantization scales); an accidental fp32 "
                "residual doubles that activation's HBM and memory "
                "bandwidth"
            ),
        ))
    return out


def _scan_residual_bytes(jx) -> int:
    """Bytes of per-iteration residuals stacked by scan equations (the
    ys outputs beyond the carry) — what survives an accumulation /
    layer scan into the backward."""
    total = 0
    for eqn in _iter_eqns(jx):
        if eqn.primitive.name != "scan":
            continue
        num_carry = int(eqn.params.get("num_carry", 0))
        for v in eqn.outvars[num_carry:]:
            total += _var_nbytes(v)
    return total


def _check_remat(entry: dict, jaxpr) -> List[Finding]:
    policy = entry.get("remat_policy")
    cap_mb = entry.get("residual_cap_mb")
    if not policy or cap_mb is None:
        return []
    jx = getattr(jaxpr, "jaxpr", jaxpr)
    resid_mb = _scan_residual_bytes(jx) / 1e6
    if resid_mb <= cap_mb:
        return []
    return [Finding(
        file=entry["file"], line=1, code="ST1004", severity="error",
        message=(
            f"entry {entry['name']!r}: checkpoint policy {policy!r} is "
            f"configured but {resid_mb:.4f} MB of scan-body residuals "
            f"still survive to the backward (cap {cap_mb:.4f} MB) — the "
            "policy is not rematerializing; activations are stored at "
            "full scale as if gradient checkpointing were off"
        ),
    )]


def _check_pool_sizing(entry: dict) -> List[Finding]:
    kc = entry.get("kv_cache")
    if not kc:
        return []
    from scaletorch_tpu.inference.kv_cache import cache_nbytes, kv_cache_bytes

    expected = kv_cache_bytes(
        kc["cfg"], kc["num_pages"], kc["page_size"], kc.get("dtype"))
    actual = cache_nbytes(entry["args"][kc["arg_index"]])
    if actual == expected:
        return []
    return [Finding(
        file=entry["file"], line=1, code="ST1005", severity="error",
        message=(
            f"entry {entry['name']!r}: engine kv_cache_bytes sizes the "
            f"page pool at {expected} bytes but the compiled entry's "
            f"pool buffers are {actual} bytes — admission math and the "
            "compiled program have drifted apart (page-budget shedding "
            "is computed from the former, XLA allocates the latter)"
        ),
    )]


def check_memory(
    entry: dict, acct: MemoryAccounting, jaxpr, compiled_text: str
) -> List[Finding]:
    """The contract checks for one compiled entry (the budget gate,
    ST1001, is separate — :func:`check_hbm_budget`)."""
    out: List[Finding] = []
    out.extend(_check_donation_bytes(entry, acct, compiled_text))
    out.extend(_check_precision(entry, jaxpr))
    out.extend(_check_remat(entry, jaxpr))
    out.extend(_check_pool_sizing(entry))
    return out


# ---- per-entry report + audit drivers ---------------------------------------

def memory_report(acct: MemoryAccounting) -> dict:
    """The budget-file row for one entry: MB ledger + which accounting
    produced it (XLA stats vs the liveness estimate are not comparable;
    the gate downgrades cross-source diffs to warnings)."""
    return {
        "argument_mb": round(acct.argument_bytes / 1e6, 4),
        "output_mb": round(acct.output_bytes / 1e6, 4),
        "temp_mb": round(acct.temp_bytes / 1e6, 4),
        "alias_mb": round(acct.alias_bytes / 1e6, 4),
        "peak_mb": round(acct.peak_bytes / 1e6, 4),
        "source": acct.source,
    }


def audit_compiled_memory(
    ce,
) -> Tuple[List[Finding], dict, List[TopAllocation]]:
    """(contract findings, budget row, top-k attribution) for one
    :class:`~.jaxpr_audit.CompiledEntry`."""
    acct, top = entry_accounting(ce)
    findings = check_memory(ce.entry, acct, ce.jaxpr, ce.compiled_text)
    return findings, memory_report(acct), top


def audit_memory_all(
    names: Optional[Sequence[str]] = None,
) -> Tuple[List[Finding], Dict[str, dict], Dict[str, List[TopAllocation]]]:
    """Compile the manifest (or the named subset) and run the memory
    audit — the standalone twin of ``jaxpr_audit.audit_all`` for tests
    and the single-tier CLI path."""
    from .jaxpr_audit import compile_entry, load_entries

    entries, findings = load_entries(names)
    reports: Dict[str, dict] = {}
    tops: Dict[str, List[TopAllocation]] = {}
    for entry in entries:
        ce, fs = compile_entry(entry)
        findings.extend(fs)
        if ce is None:
            continue
        fs, report, top = audit_compiled_memory(ce)
        findings.extend(fs)
        reports[entry["name"]] = report
        tops[entry["name"]] = top
    return findings, reports, tops


# ---- the HBM budget gate (ST1001) -------------------------------------------

def write_hbm_budget(
    path: Path, reports: Dict[str, dict],
    tolerance_pct: float = DEFAULT_TOLERANCE_PCT,
) -> None:
    """Persist per-entry memory reports as the checked-in budget."""
    try:
        import jax
        jax_version = jax.__version__
    except Exception:  # pragma: no cover — the memory tier always has jax
        jax_version = "unknown"
    # The generating jax version is stamped PER ROW, not only file-wide:
    # a scoped `--entries X --write-hbm-budget` merges fresh rows next to
    # rows measured under an older jax, and each must keep its own stamp
    # or the cross-version warning downgrade breaks for the stale ones.
    rows = {
        name: {**report, "jax": report.get("jax", jax_version)}
        for name, report in reports.items()
    }
    doc = {
        "version": 1,
        "jax": jax_version,
        "tolerance_pct": tolerance_pct,
        "note": (
            "Per-entry-point HBM budget (analysis/memory.py). Ledger "
            "from compiled.memory_analysis() on the virtual-mesh "
            "compile ('source': 'xla') or the jaxpr buffer-liveness "
            "estimator ('jaxpr-liveness'); peak = argument + temp + "
            "generated code. Regenerate after an INTENTIONAL memory "
            "change with `python -m scaletorch_tpu.analysis --tier "
            "memory --write-hbm-budget` and explain the diff in the PR."
        ),
        "entries": rows,
    }
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(doc, indent=1, sort_keys=True) + "\n",
                    encoding="utf-8")


def load_hbm_budget(path: Path) -> dict:
    """Parse the budget file; ValueError on unreadable/malformed content
    (the CLI maps that to a usage error, like a typo'd path)."""
    try:
        doc = json.loads(path.read_text(encoding="utf-8"))
    except OSError as exc:
        raise ValueError(f"cannot read hbm budget {path}: {exc}") from exc
    except json.JSONDecodeError as exc:
        raise ValueError(
            f"hbm budget {path} is not valid JSON: {exc}") from exc
    if not isinstance(doc, dict) or not isinstance(doc.get("entries"), dict):
        raise ValueError(
            f"hbm budget {path} is malformed: expected an object with an "
            "'entries' mapping"
        )
    return doc


def _top_note(tops: Optional[Dict[str, List[TopAllocation]]],
              name: str) -> str:
    top = (tops or {}).get(name) or []
    shown = [t for t in top if t.site != "<argument>"][:3]
    if not shown:
        return ""
    return " [largest live allocations: " + "; ".join(
        f"{t.nbytes / 1e6:.2f} MB {t.dtype}{list(t.shape)} at {t.site}"
        for t in shown
    ) + "]"


def check_hbm_budget(
    reports: Dict[str, dict],
    budget_doc: dict,
    *,
    tolerance_pct: Optional[float] = None,
    tops: Optional[Dict[str, List[TopAllocation]]] = None,
) -> List[Finding]:
    """Compare fresh memory reports against the checked-in budget.
    Findings land on tools/hbm_budget.json — the file a re-baseline
    would touch. Same downgrade rules as the comm budget: a different
    installed jax, or a different accounting source, reports warnings
    (re-baseline advice) instead of errors."""
    try:
        import jax
        cur_jax = jax.__version__
    except Exception:  # pragma: no cover
        cur_jax = None
    tol = (
        tolerance_pct if tolerance_pct is not None
        else float(budget_doc.get("tolerance_pct", DEFAULT_TOLERANCE_PCT))
    )
    entries = budget_doc["entries"]
    out: List[Finding] = []

    def grew(now: float, budgeted: float) -> bool:
        return now > budgeted * (1.0 + tol / 100.0) + _ABS_SLACK_MB

    def shrank(now: float, budgeted: float) -> bool:
        return now < budgeted * (1.0 - tol / 100.0) - _ABS_SLACK_MB

    for name, report in sorted(reports.items()):
        budget = entries.get(name)
        if budget is None:
            out.append(Finding(
                file=_BUDGET_FILE, line=1, code="ST1001", severity="error",
                message=(
                    f"audited entry {name!r} has no hbm budget — add it "
                    "with --write-hbm-budget so its peak memory is gated"
                ),
            ))
            continue
        # per-row jax stamp (scoped re-baselines mix generations in one
        # file); fall back to the file-wide stamp for older budgets
        row_jax = budget.get("jax", budget_doc.get("jax"))
        same_jax = cur_jax is None or row_jax in (None, cur_jax)
        same_source = report.get("source") == budget.get("source")
        soft = not (same_jax and same_source)
        severity = "warning" if soft else "error"
        drift_note = "" if not soft else (
            " [budget from "
            + (f"jax {row_jax}" if not same_jax
               else f"source {budget.get('source')!r} vs now "
                    f"{report.get('source')!r}")
            + " — if the change is environment drift, re-baseline with "
            "--write-hbm-budget]"
        )
        for field in ("peak_mb", "temp_mb", "argument_mb"):
            now_mb = float(report.get(field, 0.0))
            ref_mb = float(budget.get(field, 0.0))
            if grew(now_mb, ref_mb):
                out.append(Finding(
                    file=_BUDGET_FILE, line=1, code="ST1001",
                    severity=severity,
                    message=(
                        f"entry {name!r}: {field} over budget — "
                        f"{now_mb:.4f} MB vs budgeted {ref_mb:.4f} MB "
                        f"(tolerance {tol:g}% + {_ABS_SLACK_MB} MB)"
                        f"{_top_note(tops, name)}{drift_note}"
                    ),
                ))
        now_alias = float(report.get("alias_mb", 0.0))
        ref_alias = float(budget.get("alias_mb", 0.0))
        if shrank(now_alias, ref_alias):
            out.append(Finding(
                file=_BUDGET_FILE, line=1, code="ST1001", severity=severity,
                message=(
                    f"entry {name!r}: donation alias savings shrank — "
                    f"{now_alias:.4f} MB aliased vs budgeted "
                    f"{ref_alias:.4f} MB; the lost bytes become a second "
                    f"resident copy in HBM{drift_note}"
                ),
            ))
    return out


def check_hbm_budget_path(
    reports: Dict[str, dict], path: Path,
    tops: Optional[Dict[str, List[TopAllocation]]] = None,
) -> Tuple[List[Finding], Optional[str]]:
    """(findings, usage_error). A missing/malformed budget file is a
    usage error string (exit 2 at the CLI), not a finding crash."""
    if not path.is_file():
        return [], (
            f"hbm budget {path} not found — generate it with "
            "`python -m scaletorch_tpu.analysis --tier memory "
            "--write-hbm-budget` (or pass --no-hbm-budget to skip the "
            "gate)"
        )
    try:
        doc = load_hbm_budget(path)
    except ValueError as exc:
        return [], str(exc)
    return check_hbm_budget(reports, doc, tops=tops), None
