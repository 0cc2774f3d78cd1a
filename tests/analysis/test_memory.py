"""Memory tier (ST10xx): static HBM accounting over the REAL manifest,
compiled tiny on the 8-virtual-device CPU mesh, plus the hbm-budget
gate and the injection mutations — mirroring the PR 6 ST701/ST702
style (test_deep.py): the expensive full-manifest compile runs once per
module, each mutation pays for its own single-entry compile.
"""

import json
from pathlib import Path

import pytest

from scaletorch_tpu.analysis import memory as memory_mod
from scaletorch_tpu.analysis.jaxpr_audit import compile_entry

REPO = Path(__file__).resolve().parents[2]
HBM_BUDGET = REPO / "tools" / "hbm_budget.json"


@pytest.fixture(scope="module")
def full_memory_audit():
    findings, reports, tops = memory_mod.audit_memory_all()
    return findings, reports, tops


def _audit_one(entry):
    ce, fs = compile_entry(entry)
    assert ce is not None, [f.render() for f in fs]
    findings, report, top = memory_mod.audit_compiled_memory(ce)
    return findings, report, top


class TestManifestMemoryClean:
    def test_full_manifest_audits_clean(self, full_memory_audit):
        findings, _, _ = full_memory_audit
        assert findings == [], [f.render() for f in findings]

    def test_reports_cover_the_manifest(self, full_memory_audit):
        _, reports, _ = full_memory_audit
        assert set(reports) == {
            "spmd_train_step", "declarative_train_step",
            "paged_prefill_step", "paged_decode_step",
            "disagg_prefill_slice", "disagg_decode_slice",
        }

    def test_xla_accounting_available_on_cpu(self, full_memory_audit):
        """This environment's backend reports real stats — the liveness
        estimator is the fallback, not the norm."""
        _, reports, _ = full_memory_audit
        for name, rep in reports.items():
            assert rep["source"] == "xla", (name, rep)
            assert rep["peak_mb"] > 0, (name, rep)

    @pytest.mark.parametrize("builder", [
        "audit_entry_paged_prefill", "audit_entry_paged_decode"])
    def test_donated_cache_shows_up_as_alias_savings(
        self, full_memory_audit, builder
    ):
        """Both engine steps donate the page pool; the compiled alias
        bytes must cover it — the standing form of the ST702 one-shot."""
        from scaletorch_tpu.inference import decode

        _, reports, _ = full_memory_audit
        entry = getattr(decode, builder)()
        want = entry["donated_min_mb"]
        assert want > 0
        assert reports[entry["name"]]["alias_mb"] >= want

    def test_top_attribution_has_source_sites(self, full_memory_audit):
        """The liveness walk attributes live-at-peak buffers to source
        lines via eqn provenance — the thing XLA's stats can't do."""
        _, _, tops = full_memory_audit
        top = tops["paged_prefill_step"]
        assert top, "no top allocations recorded"
        sites = [t.site for t in top]
        assert any(".py:" in s for s in sites), sites


class TestHbmBudgetGate:
    def test_checked_in_budget_passes(self, full_memory_audit):
        _, reports, tops = full_memory_audit
        findings, usage_error = memory_mod.check_hbm_budget_path(
            reports, HBM_BUDGET, tops=tops
        )
        assert usage_error is None
        assert findings == [], [f.render() for f in findings]

    def test_doctored_budget_trips_st1001(self, full_memory_audit):
        """Shrinking the budgeted peak must trip ST1001 with top-k
        source attribution in the message."""
        _, reports, tops = full_memory_audit
        doc = json.loads(HBM_BUDGET.read_text())
        row = doc["entries"]["spmd_train_step"]
        row["peak_mb"] = row["peak_mb"] / 4.0
        row["temp_mb"] = row["temp_mb"] / 4.0
        findings = memory_mod.check_hbm_budget(reports, doc, tops=tops)
        codes = {f.code for f in findings}
        assert codes == {"ST1001"}, [f.render() for f in findings]
        assert all(f.severity == "error" for f in findings)
        assert any("largest live allocations" in f.message
                   for f in findings), [f.render() for f in findings]

    def test_lost_alias_savings_trip_st1001(self, full_memory_audit):
        _, reports, _ = full_memory_audit
        doc = json.loads(HBM_BUDGET.read_text())
        doc["entries"]["paged_decode_step"]["alias_mb"] = 5.0
        findings = memory_mod.check_hbm_budget(reports, doc)
        assert any(
            f.code == "ST1001" and "alias" in f.message for f in findings
        ), [f.render() for f in findings]

    def test_missing_entry_row_trips_st1001(self, full_memory_audit):
        _, reports, _ = full_memory_audit
        doc = json.loads(HBM_BUDGET.read_text())
        del doc["entries"]["paged_decode_step"]
        findings = memory_mod.check_hbm_budget(reports, doc)
        assert any(
            f.code == "ST1001" and "--write-hbm-budget" in f.message
            for f in findings
        ), [f.render() for f in findings]

    def test_jax_version_drift_downgrades_to_warning(
        self, full_memory_audit
    ):
        """The stamp is PER ROW (scoped re-baselines mix generations in
        one file): only the stale row's regression downgrades."""
        _, reports, _ = full_memory_audit
        doc = json.loads(HBM_BUDGET.read_text())
        doc["entries"]["spmd_train_step"]["jax"] = "0.0.0-not-this-jax"
        doc["entries"]["spmd_train_step"]["peak_mb"] /= 4.0
        doc["entries"]["paged_decode_step"]["peak_mb"] /= 4.0
        findings = memory_mod.check_hbm_budget(reports, doc)
        by_entry = {
            ("spmd" if "spmd" in f.message else "decode"): f.severity
            for f in findings
        }
        assert by_entry == {"spmd": "warning", "decode": "error"}, [
            f.render() for f in findings
        ]

    def test_source_drift_downgrades_to_warning(self, full_memory_audit):
        """A budget written from the liveness estimator is not
        comparable to XLA numbers — warn + re-baseline advice, never a
        red job nobody can fix."""
        _, reports, _ = full_memory_audit
        doc = json.loads(HBM_BUDGET.read_text())
        row = doc["entries"]["spmd_train_step"]
        row["source"] = "jaxpr-liveness"
        row["peak_mb"] /= 4.0
        findings = memory_mod.check_hbm_budget(reports, doc)
        assert findings
        for f in findings:
            if "spmd_train_step" in f.message:
                assert f.severity == "warning", f.render()

    def test_missing_budget_is_usage_error(self, full_memory_audit,
                                           tmp_path):
        _, reports, _ = full_memory_audit
        findings, usage_error = memory_mod.check_hbm_budget_path(
            reports, tmp_path / "nope.json"
        )
        assert findings == [] and usage_error is not None
        assert "--write-hbm-budget" in usage_error

    def test_malformed_budget_is_usage_error(self, full_memory_audit,
                                             tmp_path):
        bad = tmp_path / "hbm_budget.json"
        bad.write_text("{not json")
        _, reports, _ = full_memory_audit
        findings, usage_error = memory_mod.check_hbm_budget_path(
            reports, bad
        )
        assert findings == [] and usage_error is not None

    def test_scoped_write_merges_into_existing(
        self, full_memory_audit, tmp_path
    ):
        """`--entries X --write-hbm-budget` must update X's row without
        truncating the other entries' (same contract as --write-budget)."""
        from scaletorch_tpu.analysis.__main__ import main

        _, reports, _ = full_memory_audit
        path = tmp_path / "hbm_budget.json"
        stale = {
            name: {**row, "jax": "0.0.0-older-jax"}
            for name, row in reports.items()
        }
        memory_mod.write_hbm_budget(path, stale)
        rc = main([
            str(REPO / "tests" / "analysis" / "fixtures" / "clean.py"),
            "--no-baseline", "--tier", "memory",
            "--entries", "paged_decode_step", "--write-hbm-budget",
            "--hbm-budget", str(path),
        ])
        assert rc == 0
        merged = memory_mod.load_hbm_budget(path)
        assert set(merged["entries"]) == set(reports)
        # the re-baselined row carries the CURRENT jax, the untouched
        # rows keep their original stamp — a scoped write must not
        # launder stale rows into same-version comparisons
        import jax

        assert merged["entries"]["paged_decode_step"]["jax"] == \
            jax.__version__
        assert merged["entries"]["spmd_train_step"]["jax"] == \
            "0.0.0-older-jax"


class TestInjectedRegressions:
    def test_lost_donation_trips_st1002(self):
        """donate=False: the compiled module aliases nothing, so the
        declared donated bytes cannot show up as savings."""
        from scaletorch_tpu.parallel import spmd

        findings, _, _ = _audit_one(spmd.audit_entry(donate=False))
        assert any(f.code == "ST1002" for f in findings), [
            f.render() for f in findings
        ]

    def test_bf16_entry_without_injection_is_clean(self):
        from scaletorch_tpu.inference.decode import audit_entry_paged_decode

        findings, _, _ = _audit_one(audit_entry_paged_decode(
            compute_dtype="bf16"))
        assert findings == [], [f.render() for f in findings]

    def test_fp32_cast_in_bf16_entry_trips_st1003(self):
        """The motivating precision leak: a full-pool fp32 round trip
        inside a bf16-configured decode — attributed to its source line."""
        from scaletorch_tpu.inference.decode import audit_entry_paged_decode

        findings, _, _ = _audit_one(audit_entry_paged_decode(
            compute_dtype="bf16", fp32_residual=True))
        leaks = [f for f in findings if f.code == "ST1003"]
        assert leaks, [f.render() for f in findings]
        assert any("decode.py" in f.message for f in leaks), [
            f.render() for f in leaks
        ]

    @pytest.mark.parametrize("builder", [
        "audit_entry_paged_prefill", "audit_entry_paged_decode"])
    def test_shrunken_pool_trips_st1005(self, builder):
        """The engine's kv_cache_bytes says N pages, the compiled pool
        holds fewer — admission math and XLA have drifted apart. The
        whole pool at its pinned size is clean (the byte equality)."""
        from scaletorch_tpu.inference import decode

        findings, _, _ = _audit_one(getattr(decode, builder)(pool_pages=5))
        assert any(f.code == "ST1005" for f in findings), [
            f.render() for f in findings
        ]
        findings, _, _ = _audit_one(getattr(decode, builder)())
        assert findings == [], [f.render() for f in findings]


class TestSyntheticRematCheck:
    """ST1004's regression — a checkpoint policy whose scan residuals
    still survive at full-activation scale — is exercised on a
    purpose-built program (the real manifest entries audit with gc off,
    so the check is inert there, like ST703/ST704 in test_deep.py)."""

    def _entry(self, cap_mb):
        import jax
        import jax.numpy as jnp

        def f(x):
            def body(c, xi):
                h = jnp.tanh(xi @ xi.T)
                return c + h.sum(), h    # full-scale residual per layer
            out, ys = jax.lax.scan(body, 0.0, x)
            return out + ys.sum()

        return {
            "name": "synthetic_remat",
            "file": "tests/analysis/test_memory.py",
            "fn": jax.jit(f),
            "args": (jax.ShapeDtypeStruct((8, 64, 64), jnp.float32),),
            "min_devices": 1,
            "quantized_axis": None,
            "expect_donation": False,
            "hoisted_axes": (),
            "max_collective_result_mb": None,
            "remat_policy": "nothing_saveable",
            "residual_cap_mb": cap_mb,
        }

    def test_surviving_residuals_detected(self):
        findings, _, _ = _audit_one(self._entry(cap_mb=0.01))
        assert any(f.code == "ST1004" for f in findings), [
            f.render() for f in findings
        ]

    def test_generous_cap_is_silent(self):
        findings, _, _ = _audit_one(self._entry(cap_mb=100.0))
        assert findings == [], [f.render() for f in findings]


class TestLivenessEstimator:
    """The always-available fallback: a linear buffer-liveness walk
    that deliberately overestimates (no fusion, no donation reuse)."""

    def _traced(self):
        import jax
        import jax.numpy as jnp

        def f(x, y):
            a = x @ y          # temp, dies after b
            b = a * 2.0
            return b.sum(0)

        return jax.jit(f).trace(
            jax.ShapeDtypeStruct((64, 64), jnp.float32),
            jax.ShapeDtypeStruct((64, 64), jnp.float32),
        )

    def test_peak_bounds_and_fields(self):
        traced = self._traced()
        acct, top = memory_mod.estimate_jaxpr_memory(traced.jaxpr)
        args = 2 * 64 * 64 * 4
        assert acct.source == "jaxpr-liveness"
        assert acct.argument_bytes == args
        assert acct.output_bytes == 64 * 4
        # peak covers args + at least one live matmul temp
        assert acct.peak_bytes >= args + 64 * 64 * 4
        assert acct.temp_bytes == acct.peak_bytes - acct.argument_bytes

    def test_top_allocations_sorted_and_attributed(self):
        traced = self._traced()
        _, top = memory_mod.estimate_jaxpr_memory(traced.jaxpr)
        assert top
        sizes = [t.nbytes for t in top]
        assert sizes == sorted(sizes, reverse=True)
        assert any(t.site != "<argument>" for t in top)

    def test_alias_bytes_parsed_from_hlo_header(self):
        """The ST1002 fallback when memory_analysis() is absent: sum
        the flattened argument avals named by input_output_alias."""
        import jax
        import jax.numpy as jnp

        entry = {"args": (
            jax.ShapeDtypeStruct((16, 16), jnp.float32),   # idx 0: 1024 B
            jax.ShapeDtypeStruct((8,), jnp.float32),       # idx 1: 32 B
        )}
        text = ("HloModule jit_f, is_scheduled=true, input_output_alias="
                "{ {0}: (0, {}, may-alias), {1}: (1, {}, may-alias) }, "
                "entry_computation_layout={...}\n\nENTRY %main {}")
        got = memory_mod._alias_bytes_from_hlo(text, entry)
        assert got == 16 * 16 * 4 + 8 * 4
        assert memory_mod._alias_bytes_from_hlo("no alias here", entry) == 0

    def test_fallback_when_xla_stats_absent(self):
        """entry_accounting falls back to the estimator when the
        backend reports nothing."""

        class _NoStats:
            def memory_analysis(self):
                return None

        traced = self._traced()

        class _CE:
            jaxpr = traced.jaxpr
            compiled = _NoStats()
            compiled_text = ""
            entry = {}

        acct, _ = memory_mod.entry_accounting(_CE())
        assert acct.source == "jaxpr-liveness"
        assert acct.peak_bytes > 0


class TestKvCacheBytesCrossCheck:
    """Satellite fix: the engine's capacity math (`kv_cache_bytes`) and
    the buffers the compiled program actually allocates
    (`cache_nbytes` over the eval_shape tree) must agree exactly —
    page-budget admission depends on it."""

    def _cfg(self):
        import jax.numpy as jnp

        from scaletorch_tpu.models.llama import LlamaConfig

        return LlamaConfig(
            vocab_size=64, hidden_size=32, intermediate_size=64,
            num_hidden_layers=3, num_attention_heads=4,
            num_key_value_heads=2, head_dim=8,
            max_position_embeddings=128,
            dtype=jnp.float32, param_dtype=jnp.float32,
        )

    def test_paged_layout_matches(self):
        import jax
        import jax.numpy as jnp

        from scaletorch_tpu.inference.kv_cache import (
            cache_nbytes,
            init_paged_kv_cache,
            kv_cache_bytes,
        )

        cfg = self._cfg()
        pool = jax.eval_shape(
            lambda: init_paged_kv_cache(cfg, 17, 8, dtype=jnp.float32))
        assert cache_nbytes(pool) == kv_cache_bytes(
            cfg, 17, 8, jnp.float32)

    def test_bf16_halves_both_sides(self):
        import jax
        import jax.numpy as jnp

        from scaletorch_tpu.inference.kv_cache import (
            cache_nbytes,
            init_paged_kv_cache,
            kv_cache_bytes,
        )

        cfg = self._cfg()
        pool = jax.eval_shape(
            lambda: init_paged_kv_cache(cfg, 9, 8, dtype=jnp.bfloat16))
        assert cache_nbytes(pool) == kv_cache_bytes(
            cfg, 9, 8, jnp.bfloat16)
        assert cache_nbytes(pool) * 2 == kv_cache_bytes(
            cfg, 9, 8, jnp.float32)
