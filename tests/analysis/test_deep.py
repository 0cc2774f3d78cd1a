"""Deep tier: the jaxpr/HLO audit and the comm-budget gate run against
the REAL entry points, compiled tiny on the 8-virtual-device CPU mesh
(conftest.py forces ``xla_force_host_platform_device_count=8``).

The expensive part — tracing and compiling all four manifest entries —
runs once per module via the ``full_audit`` fixture; the mutation tests
pay for their own (single-entry) compiles because each injects a
different regression into the build.
"""

import json
from pathlib import Path

import pytest

from scaletorch_tpu.analysis import budget as budget_mod
from scaletorch_tpu.analysis.jaxpr_audit import (
    MANIFEST,
    audit_entry,
    audit_all,
)

REPO = Path(__file__).resolve().parents[2]
BUDGET = REPO / "tools" / "comm_budget.json"


@pytest.fixture(scope="module")
def full_audit():
    findings, reports = audit_all()
    return findings, reports


class TestManifestAuditsClean:
    def test_all_entry_points_audit_clean(self, full_audit):
        findings, _ = full_audit
        assert findings == [], [f.render() for f in findings]

    def test_reports_cover_the_manifest(self, full_audit):
        _, reports = full_audit
        assert set(reports) == {
            "spmd_train_step", "declarative_train_step",
            "paged_prefill_step", "paged_decode_step",
            "disagg_prefill_slice", "disagg_decode_slice",
        }
        assert len(MANIFEST) == 6

    def test_entries_filter_skips_unselected_builders(self):
        """A scoped run builds ONLY the selected entries (an unrelated
        builder mid-edit must not fail it) and an unknown name is an
        ST700, reported against the static manifest."""
        from scaletorch_tpu.analysis.jaxpr_audit import load_entries

        entries, errors = load_entries(["paged_decode_step"])
        assert [e["name"] for e in entries] == ["paged_decode_step"]
        assert errors == []
        entries, errors = load_entries(["nope"])
        assert entries == []
        assert len(errors) == 1 and errors[0].code == "ST700"
        assert "unknown audit entry" in errors[0].message

    def test_spmd_step_moves_int8_on_dp(self, full_audit):
        """The PR 5 attestation as a standing fact: the compiled SPMD
        step's dp edge carries s8 wire classes and the jaxpr shows dp
        collectives."""
        _, reports = full_audit
        rep = reports["spmd_train_step"]
        assert "dp" in rep["axes"] and rep["axes"]["dp"]["count"] > 0
        s8 = [k for k in rep["hlo"] if k.endswith(":s8")]
        assert s8, rep["hlo"]

    def test_inference_steps_have_zero_collectives(self, full_audit):
        """Single-device prefill/decode compile to no collectives — so
        ANY collective a future change introduces is unbudgeted by
        construction and fails the gate."""
        _, reports = full_audit
        for name in ("paged_prefill_step", "paged_decode_step"):
            assert reports[name]["hlo"] == {}, reports[name]
            assert reports[name]["total_wire_mb"] == 0.0


class TestBudgetGate:
    def test_checked_in_budget_passes(self, full_audit):
        _, reports = full_audit
        findings, usage_error = budget_mod.check_budget_path(
            reports, BUDGET
        )
        assert usage_error is None
        assert findings == [], [f.render() for f in findings]

    def test_doctored_budget_fails(self, full_audit):
        """Shrinking the budgeted bytes and dropping the s8 wire class
        must trip ST802 (regression) and ST801 (unbudgeted)."""
        _, reports = full_audit
        doc = json.loads(BUDGET.read_text())
        spmd = doc["entries"]["spmd_train_step"]
        spmd["total_wire_mb"] = spmd["total_wire_mb"] / 4.0
        spmd["hlo"] = {
            k: v for k, v in spmd["hlo"].items() if not k.endswith(":s8")
        }
        findings = budget_mod.check_budget(reports, doc)
        codes = {f.code for f in findings}
        assert "ST801" in codes and "ST802" in codes, [
            f.render() for f in findings
        ]

    def test_missing_budget_is_usage_error(self, full_audit, tmp_path):
        _, reports = full_audit
        findings, usage_error = budget_mod.check_budget_path(
            reports, tmp_path / "nope.json"
        )
        assert findings == [] and usage_error is not None
        assert "--write-budget" in usage_error

    def test_malformed_budget_is_usage_error(self, full_audit, tmp_path):
        bad = tmp_path / "comm_budget.json"
        bad.write_text("{not json")
        _, reports = full_audit
        findings, usage_error = budget_mod.check_budget_path(
            reports, bad
        )
        assert findings == [] and usage_error is not None

    def test_scoped_write_budget_merges_into_existing(
        self, full_audit, tmp_path
    ):
        """`--entries X --write-budget` must update X's budget without
        truncating the other entries' (the file is the whole fleet's
        contract, a scoped re-baseline touches only its slice)."""
        from scaletorch_tpu.analysis.__main__ import main

        _, reports = full_audit
        path = tmp_path / "comm_budget.json"
        budget_mod.write_budget(path, reports)
        rc = main([
            str(REPO / "tests" / "analysis" / "fixtures" / "clean.py"),
            "--no-baseline", "--tier", "deep",
            "--entries", "paged_decode_step", "--write-budget",
            "--budget", str(path),
        ])
        assert rc == 0
        merged = budget_mod.load_budget(path)
        assert set(merged["entries"]) == set(reports)


class TestInjectedRegressions:
    def test_fp32_mutation_fails_dtype_check_and_budget(self):
        """The motivating failure: int8 configured as the entry's
        contract, fp32 actually lowered on the dp edge. Both detectors
        must fire — ST701 from the jaxpr walk, and a budget failure
        (the fp32 dp mean regresses all-reduce:f32 bytes vs the
        checked-in budget)."""
        from scaletorch_tpu.parallel import spmd

        entry = spmd.audit_entry(grad_allreduce_dtype="fp32")
        findings, report = audit_entry(entry)
        assert any(f.code == "ST701" for f in findings), [
            f.render() for f in findings
        ]
        budget_findings, usage_error = budget_mod.check_budget_path(
            {"spmd_train_step": report}, BUDGET
        )
        assert usage_error is None
        assert any(f.code in ("ST801", "ST802") for f in budget_findings), [
            f.render() for f in budget_findings
        ]

    def test_lost_donation_detected(self):
        from scaletorch_tpu.parallel import spmd

        entry = spmd.audit_entry(donate=False)
        findings, _ = audit_entry(entry)
        assert any(f.code == "ST702" for f in findings), [
            f.render() for f in findings
        ]


class TestSyntheticJaxprChecks:
    """Checks whose regressions the real entry points (correctly) never
    exhibit, exercised on a purpose-built program."""

    def _synthetic_entry(self, cap_mb):
        import numpy as np

        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P

        mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ("dp",))

        def body(x):
            def step(carry, xi):
                # the per-microbatch reduction the schedule says must be
                # hoisted out of the accumulation loop
                return carry + jax.lax.psum(xi, "dp"), None

            # fresh zeros, not zeros_like(x[0]): the carry is a psum
            # result (invariant over dp) and zeros_like would inherit
            # x's varying type — ill-typed under shard_map's VMA check
            out, _ = jax.lax.scan(
                step, jnp.zeros(x.shape[1:], x.dtype), x)
            return out

        fn = jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=P(None, "dp"), out_specs=P(),
        ))
        return {
            "name": "synthetic_scan_psum",
            "file": "tests/analysis/test_deep.py",
            "fn": fn,
            "args": (jax.ShapeDtypeStruct((4, 8), jnp.float32),),
            "min_devices": 8,
            "quantized_axis": None,
            "expect_donation": False,
            "hoisted_axes": ("dp",),
            "max_collective_result_mb": cap_mb,
        }

    def test_collective_inside_scan_detected(self):
        findings, _ = audit_entry(self._synthetic_entry(cap_mb=100.0))
        assert any(f.code == "ST703" for f in findings), [
            f.render() for f in findings
        ]

    def test_replication_cap_detected(self):
        findings, _ = audit_entry(self._synthetic_entry(cap_mb=1e-9))
        assert any(f.code == "ST704" for f in findings), [
            f.render() for f in findings
        ]


@pytest.mark.slow
class TestDeepCli:
    def test_tier_deep_cli_is_clean(self):
        """The exact CI deep-lint gate, end to end in a subprocess."""
        import subprocess
        import sys

        proc = subprocess.run(
            [sys.executable, "-m", "scaletorch_tpu.analysis",
             "scaletorch_tpu/", "tools/", "--tier", "deep"],
            cwd=REPO, capture_output=True, text=True, timeout=900,
            env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"},
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
