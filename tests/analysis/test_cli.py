"""CLI contract: output format, exit codes, baseline round-trip."""

import json
from pathlib import Path

from scaletorch_tpu.analysis import Finding, save_baseline, split_by_baseline
from scaletorch_tpu.analysis.__main__ import main

FIXTURES = Path(__file__).parent / "fixtures"


def run_cli(capsys, *argv):
    rc = main(list(argv))
    captured = capsys.readouterr()
    return rc, captured.out, captured.err


class TestExitCodes:
    def test_clean_file_exits_zero(self, capsys):
        rc, out, _ = run_cli(capsys, str(FIXTURES / "clean.py"), "--no-baseline")
        assert rc == 0 and out == ""

    def test_findings_exit_one(self, capsys):
        rc, out, _ = run_cli(
            capsys, str(FIXTURES / "bad_sharding.py"), "--no-baseline"
        )
        assert rc == 1
        assert "ST101" in out

    def test_unknown_pass_exits_two(self, capsys):
        rc, _, err = run_cli(
            capsys, str(FIXTURES / "clean.py"), "--select", "nonsense"
        )
        assert rc == 2
        assert "unknown pass" in err

    def test_nonexistent_path_exits_two(self, capsys):
        """A typo'd path must not turn the gate silently green."""
        rc, _, err = run_cli(capsys, "no_such_dir_typo", "--no-baseline")
        assert rc == 2
        assert "no_such_dir_typo" in err

    def test_syntax_error_reported_not_crash(self, capsys, tmp_path):
        bad = tmp_path / "broken.py"
        bad.write_text("def f(:\n")
        rc, out, _ = run_cli(capsys, str(bad), "--no-baseline")
        assert rc == 1
        assert "JL000" in out


class TestOutputFormat:
    def test_text_format_is_file_line_code_severity(self, capsys):
        rc, out, _ = run_cli(
            capsys, str(FIXTURES / "bad_donation.py"), "--no-baseline"
        )
        line = out.splitlines()[0]
        # file:line: CODE severity message
        loc, rest = line.split(": ", 1)
        assert loc.endswith("bad_donation.py:18")
        code, severity = rest.split(" ")[:2]
        assert code == "ST401" and severity == "error"

    def test_json_format(self, capsys):
        rc, out, _ = run_cli(
            capsys, str(FIXTURES / "bad_retrace.py"), "--no-baseline",
            "--format", "json",
        )
        data = json.loads(out)
        assert rc == 1 and data
        assert {"file", "line", "code", "severity", "message"} <= set(data[0])

    def test_select_restricts_passes(self, capsys):
        rc, out, _ = run_cli(
            capsys, str(FIXTURES / "bad_sharding.py"), "--no-baseline",
            "--select", "donation",
        )
        assert rc == 0 and out == ""


class TestSelectFamilies:
    def test_family_prefix_selects_concurrency(self, capsys):
        rc, out, _ = run_cli(
            capsys, str(FIXTURES / "bad_concurrency.py"), "--no-baseline",
            "--select", "ST9",
        )
        assert rc == 1
        assert "ST901" in out and "ST904" in out

    def test_family_is_case_insensitive(self, capsys):
        rc_lower, out_lower, _ = run_cli(
            capsys, str(FIXTURES / "bad_concurrency.py"), "--no-baseline",
            "--select", "st9",
        )
        rc_code, out_code, _ = run_cli(
            capsys, str(FIXTURES / "bad_concurrency.py"), "--no-baseline",
            "--select", "ST901",
        )
        rc_name, out_name, _ = run_cli(
            capsys, str(FIXTURES / "bad_concurrency.py"), "--no-baseline",
            "--select", "Concurrency,Telemetry-Kinds",
        )
        assert rc_lower == rc_code == rc_name == 1
        assert out_lower == out_code == out_name

    def test_family_selects_other_passes_off(self, capsys):
        rc, out, _ = run_cli(
            capsys, str(FIXTURES / "bad_sharding.py"), "--no-baseline",
            "--select", "ST9",
        )
        assert rc == 0 and out == ""

    def test_unknown_family_exits_two_listing_valid(self, capsys):
        """A typo'd selector must be a loud usage error naming every
        valid family — never a silently-green empty selection."""
        rc, _, err = run_cli(
            capsys, str(FIXTURES / "clean.py"), "--select", "ST0",
        )
        assert rc == 2
        assert "ST9" in err and "ST1" in err  # the valid-family list

    def test_family_with_trailing_garbage_rejected(self, capsys):
        """'ST9q' must not silently match family ST9 and run green —
        only exact 'STn' / full 'STnxx' tokens are families."""
        for typo in ("ST9q", "st12", "ST9001"):
            rc, _, err = run_cli(
                capsys, str(FIXTURES / "clean.py"), "--select", typo,
            )
            assert rc == 2, typo
            assert "unknown pass or family" in err, typo

    def test_deep_family_points_at_deep_tier(self, capsys):
        rc, _, err = run_cli(
            capsys, str(FIXTURES / "clean.py"), "--select", "ST7",
        )
        assert rc == 2
        assert "--tier deep" in err

    def test_memory_family_points_at_memory_tier(self, capsys):
        """ST10/ST1001 are memory-tier codes, not AST passes — like
        ST7/ST8, selecting them must point at the tier, and ST10 must
        NOT parse as the ST1 sharding family."""
        for sel in ("ST10", "st1001"):
            rc, _, err = run_cli(
                capsys, str(FIXTURES / "clean.py"), "--select", sel,
            )
            assert rc == 2, sel
            assert "--tier memory" in err, (sel, err)


class TestTierList:
    def test_unknown_tier_exits_two(self, capsys):
        """A typo'd tier must be a loud usage error naming the valid
        tiers — never a silently-green partial run."""
        rc, _, err = run_cli(
            capsys, str(FIXTURES / "clean.py"), "--tier", "nonsense",
        )
        assert rc == 2
        assert "unknown tier" in err and "memory" in err

    def test_unknown_member_of_comma_list_exits_two(self, capsys):
        rc, _, err = run_cli(
            capsys, str(FIXTURES / "clean.py"), "--tier", "deep,nonsense",
        )
        assert rc == 2
        assert "'nonsense'" in err

    def test_empty_tier_exits_two(self, capsys):
        rc, _, err = run_cli(
            capsys, str(FIXTURES / "clean.py"), "--tier", ",",
        )
        assert rc == 2
        assert "unknown tier" in err

    def test_ast_concurrency_list_runs_all_ast_passes(self, capsys):
        """'ast' in the list wins over the concurrency narrowing: the
        ST1xx fixture must still be flagged."""
        rc, out, _ = run_cli(
            capsys, str(FIXTURES / "bad_sharding.py"), "--no-baseline",
            "--tier", "ast,concurrency",
        )
        assert rc == 1
        assert "ST101" in out

    def test_tier_tag_in_summary_names_the_list(self, capsys):
        rc, _, err = run_cli(
            capsys, str(FIXTURES / "clean.py"), "--no-baseline",
            "--tier", "concurrency",
        )
        assert rc == 0
        assert "[concurrency]" in err

    def test_hbm_flags_need_memory_tier(self, capsys):
        for flag in (["--write-hbm-budget"], ["--no-hbm-budget"],
                     ["--hbm-budget", "x.json"]):
            rc, _, err = run_cli(
                capsys, str(FIXTURES / "clean.py"), *flag
            )
            assert rc == 2, flag
            assert "--tier memory" in err

    def test_comm_budget_flags_still_need_deep_tier(self, capsys):
        """--tier memory alone must not unlock the comm-budget flags."""
        rc, _, err = run_cli(
            capsys, str(FIXTURES / "clean.py"),
            "--tier", "memory", "--write-budget",
        )
        assert rc == 2
        assert "--tier deep" in err


class TestConcurrencyTier:
    def test_tier_runs_only_st9_family(self, capsys):
        # bad_sharding.py is full of ST1xx, none of which run here
        rc, out, _ = run_cli(
            capsys, str(FIXTURES / "bad_sharding.py"), "--no-baseline",
            "--tier", "concurrency",
        )
        assert rc == 0 and out == ""

    def test_tier_finds_concurrency_bugs(self, capsys):
        rc, out, err = run_cli(
            capsys, str(FIXTURES / "bad_concurrency.py"), "--no-baseline",
            "--tier", "concurrency",
        )
        assert rc == 1
        assert "ST901" in out
        assert "[concurrency]" in err

    def test_select_narrows_within_tier(self, capsys):
        rc, out, _ = run_cli(
            capsys, str(FIXTURES / "bad_kinds.py"), "--no-baseline",
            "--tier", "concurrency", "--select", "telemetry-kinds",
        )
        assert rc == 1 and "ST907" in out

    def test_foreign_select_inside_tier_is_usage_error(self, capsys):
        rc, _, err = run_cli(
            capsys, str(FIXTURES / "clean.py"),
            "--tier", "concurrency", "--select", "sharding",
        )
        assert rc == 2
        assert "selects nothing" in err


class TestOwnershipTier:
    def test_tier_finds_ownership_bugs(self, capsys):
        rc, out, err = run_cli(
            capsys, str(FIXTURES / "bad_ownership.py"), "--no-baseline",
            "--tier", "ownership",
        )
        assert rc == 1
        assert "ST1101" in out and "ST1105" in out
        assert "[ownership]" in err

    def test_tier_runs_only_st11_family(self, capsys):
        # bad_sharding.py is full of ST1xx AST findings, none run here
        rc, out, _ = run_cli(
            capsys, str(FIXTURES / "bad_sharding.py"), "--no-baseline",
            "--tier", "ownership",
        )
        assert rc == 0 and out == ""

    def test_three_tier_composition_single_process(self, capsys):
        """--tier ast,concurrency,ownership runs all three pools in one
        invocation: AST, ST9xx and ST11xx findings all surface."""
        rc, out, _ = run_cli(
            capsys,
            str(FIXTURES / "bad_sharding.py"),
            str(FIXTURES / "bad_concurrency.py"),
            str(FIXTURES / "bad_ownership.py"),
            "--no-baseline", "--tier", "ast,concurrency,ownership",
        )
        assert rc == 1
        assert "ST101" in out and "ST901" in out and "ST1101" in out

    def test_three_tier_composition_clean(self, capsys):
        rc, out, _ = run_cli(
            capsys, str(FIXTURES / "clean_ownership.py"), "--no-baseline",
            "--tier", "ast,concurrency,ownership",
        )
        assert rc == 0 and out == ""

    def test_st11_family_points_at_ownership_tier(self, capsys):
        """ST11/ST1101 are ownership-tier codes — like ST7/ST10,
        selecting them must point at the tier, and ST11 must NOT parse
        as the ST1 sharding family."""
        for sel in ("ST11", "st1101"):
            rc, _, err = run_cli(
                capsys, str(FIXTURES / "clean.py"), "--select", sel,
            )
            assert rc == 2, sel
            assert "--tier ownership" in err, (sel, err)

    def test_select_by_pass_name_works_from_default_tier(self, capsys):
        rc, out, _ = run_cli(
            capsys, str(FIXTURES / "bad_ownership.py"), "--no-baseline",
            "--select", "ownership",
        )
        assert rc == 1 and "ST1101" in out

    def test_foreign_select_inside_tier_is_usage_error(self, capsys):
        rc, _, err = run_cli(
            capsys, str(FIXTURES / "clean.py"),
            "--tier", "ownership", "--select", "sharding",
        )
        assert rc == 2
        assert "selects nothing" in err

    def test_unknown_tier_listing_includes_ownership(self, capsys):
        rc, _, err = run_cli(
            capsys, str(FIXTURES / "clean.py"), "--tier", "nonsense",
        )
        assert rc == 2
        assert "ownership" in err


class TestSarifFormat:
    def _sarif(self, capsys, *extra):
        rc, out, _ = run_cli(
            capsys, str(FIXTURES / "bad_ownership.py"), "--no-baseline",
            "--tier", "ownership", "--format", "sarif", *extra,
        )
        return rc, out

    def test_shape(self, capsys):
        rc, out = self._sarif(capsys)
        doc = json.loads(out)
        assert rc == 1
        assert doc["version"] == "2.1.0"
        driver = doc["runs"][0]["tool"]["driver"]
        assert driver["name"] == "jaxlint"
        results = doc["runs"][0]["results"]
        assert results
        r = results[0]
        assert r["ruleId"].startswith("ST11")
        assert r["level"] == "error"
        loc = r["locations"][0]["physicalLocation"]
        assert loc["artifactLocation"]["uri"].endswith("bad_ownership.py")
        assert loc["region"]["startLine"] >= 1
        rule_ids = [rule["id"] for rule in driver["rules"]]
        assert rule_ids == sorted(set(rule_ids))

    def test_byte_stable_across_runs(self, capsys):
        """No timestamps or dict-order jitter: two runs over the same
        tree must produce identical bytes (CI artifact diffing)."""
        _, first = self._sarif(capsys)
        _, second = self._sarif(capsys)
        assert first == second

    def test_clean_run_is_valid_empty_sarif(self, capsys):
        rc, out, err = run_cli(
            capsys, str(FIXTURES / "clean_ownership.py"), "--no-baseline",
            "--tier", "ownership", "--format", "sarif",
        )
        doc = json.loads(out)
        assert rc == 0
        assert doc["runs"][0]["results"] == []
        # summary line would corrupt a redirected .sarif file
        assert "jaxlint:" not in err


class TestGithubFormat:
    def test_error_and_warning_annotations(self, capsys):
        rc, out, _ = run_cli(
            capsys, str(FIXTURES / "bad_symmetry.py"), "--no-baseline",
            "--format", "github",
        )
        assert rc == 1
        lines = out.splitlines()
        assert any(
            ln.startswith("::error file=") and "title=jaxlint ST601" in ln
            for ln in lines
        )
        assert any(ln.startswith("::warning file=") for ln in lines)
        # every annotation carries a file and a line anchor
        assert all(
            ",line=" in ln for ln in lines if ln.startswith("::")
        )

    def test_json_format_unchanged_by_new_flags(self, capsys):
        """--format json stays byte-compatible: same keys, same shape."""
        rc, out, _ = run_cli(
            capsys, str(FIXTURES / "bad_retrace.py"), "--no-baseline",
            "--format", "json",
        )
        data = json.loads(out)
        assert rc == 1 and data
        assert set(data[0]) == {"file", "line", "code", "severity",
                                "message"}


class TestMalformedBaseline:
    def test_invalid_json_is_usage_error(self, capsys, tmp_path):
        bad = tmp_path / "baseline.json"
        bad.write_text("{not json", encoding="utf-8")
        rc, _, err = run_cli(
            capsys, str(FIXTURES / "clean.py"), "--baseline", str(bad)
        )
        assert rc == 2
        assert "malformed" in err and "Traceback" not in err

    def test_wrong_shape_is_usage_error(self, capsys, tmp_path):
        bad = tmp_path / "baseline.json"
        bad.write_text('{"findings": "oops"}', encoding="utf-8")
        rc, _, err = run_cli(
            capsys, str(FIXTURES / "clean.py"), "--baseline", str(bad)
        )
        assert rc == 2
        assert "malformed" in err

    def test_missing_explicit_baseline_is_usage_error(self, capsys, tmp_path):
        rc, _, err = run_cli(
            capsys, str(FIXTURES / "clean.py"),
            "--baseline", str(tmp_path / "nope.json"),
        )
        assert rc == 2
        assert "unreadable" in err

    def test_deep_flags_need_deep_tier(self, capsys):
        for flag in (["--write-budget"], ["--no-budget"],
                     ["--budget", "x.json"], ["--entries", "paged_decode_step"]):
            rc, _, err = run_cli(
                capsys, str(FIXTURES / "clean.py"), *flag
            )
            assert rc == 2, flag
            assert "--tier deep" in err


class TestBaseline:
    def test_write_then_gate_passes(self, capsys, tmp_path):
        baseline = tmp_path / "baseline.json"
        rc, _, _ = run_cli(
            capsys, str(FIXTURES / "bad_trace.py"),
            "--baseline", str(baseline), "--write-baseline",
        )
        assert rc == 0
        entries = json.loads(baseline.read_text())["findings"]
        assert entries and all(
            {"file", "code", "message"} <= set(e) for e in entries
        )
        rc, out, err = run_cli(
            capsys, str(FIXTURES / "bad_trace.py"), "--baseline", str(baseline)
        )
        assert rc == 0 and out == ""
        assert "baselined" in err

    def test_new_finding_still_fails_with_baseline(self, capsys, tmp_path):
        baseline = tmp_path / "baseline.json"
        run_cli(
            capsys, str(FIXTURES / "bad_trace.py"),
            "--baseline", str(baseline), "--write-baseline",
        )
        rc, out, _ = run_cli(
            capsys, str(FIXTURES / "bad_trace.py"),
            str(FIXTURES / "bad_prng.py"), "--baseline", str(baseline),
        )
        assert rc == 1
        assert "bad_prng" in out and "bad_trace" not in out

    def test_extra_axes_flag(self, capsys, tmp_path):
        f = tmp_path / "custom.py"
        f.write_text(
            "from jax.sharding import PartitionSpec as P\n"
            "SPEC = P('stage', None)\n"
        )
        rc1, _, _ = run_cli(capsys, str(f), "--no-baseline")
        rc2, _, _ = run_cli(
            capsys, str(f), "--no-baseline", "--extra-axes", "stage"
        )
        assert (rc1, rc2) == (1, 0)


class TestBaselineBudget:
    def test_duplicate_findings_consume_budget(self):
        f = Finding(file="a.py", line=1, code="ST101", severity="error",
                    message="m")
        dup = Finding(file="a.py", line=9, code="ST101", severity="error",
                      message="m")
        entries = [{"file": "a.py", "code": "ST101", "message": "m"}]
        new, suppressed = split_by_baseline([f, dup], entries)
        assert len(suppressed) == 1 and len(new) == 1

    def test_save_baseline_sorted_and_stable(self, tmp_path):
        p = tmp_path / "b.json"
        fs = [
            Finding(file="b.py", line=2, code="ST201", severity="error",
                    message="x"),
            Finding(file="a.py", line=5, code="ST101", severity="error",
                    message="y"),
        ]
        save_baseline(p, fs)
        entries = json.loads(p.read_text())["findings"]
        assert [e["file"] for e in entries] == ["a.py", "b.py"]
