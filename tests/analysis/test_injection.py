"""The sharding pass catches bugs injected into the REAL framework
sources — the typo-means-replicated class the pass exists for.

Each test copies a production module, injects one character-level bug,
and asserts the pass reports it (and nothing else new) against the
same module set the CI gate lints.
"""

from pathlib import Path

from scaletorch_tpu.analysis import analyze, collect_files

REPO = Path(__file__).resolve().parents[2]
PKG = REPO / "scaletorch_tpu"


def _analyze_with(tmp_path, mutated_name, mutated_src, companions):
    mutated = tmp_path / mutated_name
    mutated.write_text(mutated_src, encoding="utf-8")
    paths = [str(mutated)] + [str(PKG / c) for c in companions]
    modules, errors = collect_files(paths)
    assert not errors
    return analyze(modules, select=["sharding"])


class TestInjectedAxisTypo:
    COMPANIONS = ["parallel/mesh.py", "models/llama.py"]

    def test_llama_param_specs_axis_typo_detected(self, tmp_path):
        src = (PKG / "parallel" / "tensor_parallel.py").read_text()
        needle = 'tp_axis: Optional[str] = "tp"'
        assert needle in src, "llama_param_specs signature moved; update test"
        findings = _analyze_with(
            tmp_path, "tensor_parallel.py",
            src.replace(needle, 'tp_axis: Optional[str] = "tpq"'),
            self.COMPANIONS,
        )
        assert any(
            f.code == "ST101" and "'tpq'" in f.message for f in findings
        ), [f.render() for f in findings]

    def test_unmutated_source_is_clean(self, tmp_path):
        src = (PKG / "parallel" / "tensor_parallel.py").read_text()
        findings = _analyze_with(
            tmp_path, "tensor_parallel.py", src, self.COMPANIONS
        )
        assert findings == [], [f.render() for f in findings]

    def test_llama_param_specs_key_typo_detected(self, tmp_path):
        src = (PKG / "parallel" / "tensor_parallel.py").read_text()
        needle = '"q_proj": P(pstg, None, t)'
        assert needle in src, "llama_param_specs body moved; update test"
        findings = _analyze_with(
            tmp_path, "tensor_parallel.py",
            src.replace(needle, '"q_porj": P(pstg, None, t)'),
            self.COMPANIONS,
        )
        assert any(
            f.code == "ST102" and "'q_porj'" in f.message for f in findings
        ), [f.render() for f in findings]

    def test_paged_kv_cache_specs_axis_typo_detected(self, tmp_path):
        src = (PKG / "inference" / "kv_cache.py").read_text()
        needle = 'tp_axis: Optional[str] = "tp"'
        assert needle in src, "paged_kv_cache_specs signature moved; update test"
        findings = _analyze_with(
            tmp_path, "kv_cache.py",
            src.replace(needle, 'tp_axis: Optional[str] = "tb"', 1),
            ["parallel/mesh.py"],
        )
        assert any(
            f.code == "ST101" and "'tb'" in f.message for f in findings
        ), [f.render() for f in findings]


class TestInjectedDivergentGather:
    """The ST6xx pass catches a host-divergence bug injected into the
    REAL resilience module: a DecisionBus gather call site wrapped in
    ``if process_index() == 0:`` — the one-sided decision that wedges
    the fleet (the static dual of the HangWatchdog)."""

    SRC = PKG / "resilience_distributed.py"
    NEEDLE = "        observations = self.bus.all_gather(local)"

    def _symmetry(self, tmp_path, src):
        mutated = tmp_path / "resilience_distributed.py"
        mutated.write_text(src, encoding="utf-8")
        modules, errors = collect_files([str(mutated)])
        assert not errors
        return analyze(modules, select=["symmetry"])

    def test_divergent_gather_detected(self, tmp_path):
        src = self.SRC.read_text()
        assert self.NEEDLE in src, "after_step gather moved; update test"
        guarded = (
            "        import jax\n"
            "        if jax.process_index() == 0:\n"
            "            observations = self.bus.all_gather(local)\n"
        )
        findings = self._symmetry(
            tmp_path, src.replace(self.NEEDLE, guarded.rstrip("\n"))
        )
        assert any(
            f.code == "ST601" and "all_gather" in f.message
            for f in findings
        ), [f.render() for f in findings]

    def test_unmutated_resilience_modules_are_clean(self, tmp_path):
        """The real coordinated-decision protocol lints clean: the pass
        proves the absence of the bug class in the modules that carry
        the fleet's collectives."""
        for rel in ("resilience_distributed.py", "utils/checkpoint.py",
                    "dist.py", "trainer/trainer.py"):
            modules, errors = collect_files([str(PKG / rel)])
            assert not errors
            findings = analyze(modules, select=["symmetry"])
            assert findings == [], [f.render() for f in findings]


class TestInjectedSignalHandlerLock:
    """ST904 catches the PR 8 SpanTracer bug re-injected into the REAL
    module: reverting the tracer's RLock to a plain Lock makes the
    SIGUSR1 live-snapshot path (LiveSnapshotter._handle -> snapshot_fn
    -> Telemetry.span_tail -> SpanTracer.tail) acquire a non-reentrant
    lock the main emit path also holds — the deadlock human review
    caught, now caught statically."""

    COMPANIONS = ["telemetry/profiling.py", "telemetry/__init__.py",
                  "trainer/trainer.py"]
    SRC = PKG / "telemetry" / "spans.py"
    NEEDLE = "self._lock = threading.RLock()"

    def _concurrency(self, tmp_path, src):
        mutated = tmp_path / "spans.py"
        mutated.write_text(src, encoding="utf-8")
        paths = [str(mutated)] + [str(PKG / c) for c in self.COMPANIONS]
        modules, errors = collect_files(paths)
        assert not errors
        return analyze(modules, select=["concurrency"])

    def test_rlock_reverted_to_lock_detected(self, tmp_path):
        src = self.SRC.read_text()
        assert self.NEEDLE in src, "SpanTracer lock moved; update test"
        findings = self._concurrency(
            tmp_path, src.replace(self.NEEDLE,
                                  "self._lock = threading.Lock()")
        )
        st904 = [f for f in findings if f.code == "ST904"]
        assert st904, [f.render() for f in findings]
        assert any("_handle" in f.message and "SpanTracer._lock" in f.message
                   for f in st904), [f.render() for f in st904]

    def test_unmutated_telemetry_chain_is_clean(self, tmp_path):
        findings = self._concurrency(tmp_path, self.SRC.read_text())
        assert findings == [], [f.render() for f in findings]


class TestInjectedUnlockedReap:
    """ST901 catches the gateway's dead-worker reap race re-injected
    into the REAL module: removing the `with self._reap_lock:` guard in
    EngineWorker._reap_stale leaves `_handlers` mutated unlocked from
    both the worker thread and the caller-side reap — the race human
    review caught in PR 11."""

    SRC = PKG / "serving" / "gateway.py"
    NEEDLE = "        with self._reap_lock:"

    def _concurrency(self, tmp_path, src):
        mutated = tmp_path / "gateway.py"
        mutated.write_text(src, encoding="utf-8")
        modules, errors = collect_files([str(mutated)])
        assert not errors
        return analyze(modules, select=["concurrency"])

    def test_reap_lock_removal_detected(self, tmp_path):
        src = self.SRC.read_text()
        assert self.NEEDLE in src, "_reap_stale lock moved; update test"
        # `if True:` keeps the body's indentation valid while deleting
        # the serialization — exactly the pre-review code shape
        findings = self._concurrency(
            tmp_path, src.replace(self.NEEDLE, "        if True:")
        )
        st901 = [f for f in findings if f.code == "ST901"]
        assert any("_handlers" in f.message for f in st901), \
            [f.render() for f in findings]

    def test_unmutated_gateway_is_clean(self, tmp_path):
        """The real trampoline + reap-lock discipline lints clean: the
        pass proves the absence of the bug class in the module that
        carries the serving path's concurrency."""
        findings = self._concurrency(tmp_path, self.SRC.read_text())
        assert findings == [], [f.render() for f in findings]


class TestInjectedRetireLeak:
    """ST1101 catches a deleted release in the REAL retire path: without
    the `self.allocator.release(p)` loop, `_retire_slot` empties the
    owning `_slot_pages[i]` container and the slot's pages leak from the
    pool — the exact conservation bug `check_conservation` would only
    catch at runtime."""

    COMPANIONS = ["inference/kv_cache.py"]
    SRC = PKG / "inference" / "engine.py"
    NEEDLE = (
        "        for p in self._slot_pages[i]:\n"
        "            self.allocator.release(p)\n"
    )

    def _ownership(self, tmp_path, src):
        mutated = tmp_path / "engine.py"
        mutated.write_text(src, encoding="utf-8")
        paths = [str(mutated)] + [str(PKG / c) for c in self.COMPANIONS]
        modules, errors = collect_files(paths)
        assert not errors
        return analyze(modules, select=["ownership"])

    def test_deleted_release_loop_detected(self, tmp_path):
        src = self.SRC.read_text()
        assert self.NEEDLE in src, "_retire_slot release moved; update test"
        findings = self._ownership(tmp_path, src.replace(self.NEEDLE, "", 1))
        assert [f.code for f in findings] == ["ST1101"], \
            [f.render() for f in findings]
        assert "_slot_pages" in findings[0].message

    def test_unmutated_engine_is_clean(self, tmp_path):
        findings = self._ownership(tmp_path, self.SRC.read_text())
        assert findings == [], [f.render() for f in findings]


class TestInjectedRollbackInversion:
    """ST1105 catches the PR 19 rollback discipline inverted in the REAL
    handoff: releasing the prefill side's pages (the transfer source,
    `h.pages`) before the decode side's fresh reservation (`pages`)
    breaks destination-before-source — a second fault between the two
    loops orphans pages that still have a live owner."""

    COMPANIONS = ["inference/engine.py", "inference/kv_cache.py"]
    SRC = PKG / "inference" / "disagg.py"
    HEALTHY = (
        "            for p in pages:\n"
        "                self.allocator.release(p)\n"
        "            for p in h.pages:\n"
        "                self.prefill_allocator.release(p)\n"
    )
    SWAPPED = (
        "            for p in h.pages:\n"
        "                self.prefill_allocator.release(p)\n"
        "            for p in pages:\n"
        "                self.allocator.release(p)\n"
    )

    def _ownership(self, tmp_path, src):
        mutated = tmp_path / "disagg.py"
        mutated.write_text(src, encoding="utf-8")
        paths = [str(mutated)] + [str(PKG / c) for c in self.COMPANIONS]
        modules, errors = collect_files(paths)
        assert not errors
        return analyze(modules, select=["ownership"])

    def test_inverted_rollback_order_detected(self, tmp_path):
        src = self.SRC.read_text()
        assert self.HEALTHY in src, "_try_handoff rollback moved; update test"
        findings = self._ownership(
            tmp_path, src.replace(self.HEALTHY, self.SWAPPED, 1))
        assert [f.code for f in findings] == ["ST1105"], \
            [f.render() for f in findings]
        assert "h.pages" in findings[0].message

    def test_unmutated_disagg_is_clean(self, tmp_path):
        findings = self._ownership(tmp_path, self.SRC.read_text())
        assert findings == [], [f.render() for f in findings]


class TestRepoGate:
    def test_package_and_tools_lint_clean_with_baseline(self):
        """The exact CI gate: repo findings minus baseline is empty."""
        from scaletorch_tpu.analysis import load_baseline, split_by_baseline

        modules, errors = collect_files(
            [str(PKG), str(REPO / "tools")], root=REPO
        )
        assert not errors, [e.render() for e in errors]
        findings = analyze(modules)
        baseline_path = REPO / "tools" / "jaxlint_baseline.json"
        entries = load_baseline(baseline_path) if baseline_path.is_file() else []
        new, _ = split_by_baseline(findings, entries)
        assert new == [], [f.render() for f in new]

    def test_concurrency_tier_cli_gate(self, capsys):
        """The exact CI invocation: `python -m scaletorch_tpu.analysis
        --tier concurrency scaletorch_tpu/ tools/` exits 0 with zero
        findings on the repo."""
        import os

        from scaletorch_tpu.analysis.__main__ import main

        cwd = os.getcwd()
        os.chdir(REPO)
        try:
            rc = main(["--tier", "concurrency", "scaletorch_tpu/",
                       "tools/"])
        finally:
            os.chdir(cwd)
        out = capsys.readouterr().out
        assert rc == 0 and out == "", out

    def test_ownership_tier_cli_gate(self, capsys):
        """The exact CI invocation: `--tier ownership` exits 0 with zero
        findings over the package, tools and scripts."""
        import os

        from scaletorch_tpu.analysis.__main__ import main

        cwd = os.getcwd()
        os.chdir(REPO)
        try:
            rc = main(["--tier", "ownership", "scaletorch_tpu/", "tools/",
                       "scripts/"])
        finally:
            os.chdir(cwd)
        out = capsys.readouterr().out
        assert rc == 0 and out == "", out
