"""Tools tier: verify_weights self-test + profile breakdown math.

(The bench tools are thin CLIs over scaletorch_tpu.benchmark, covered by
tests/test_benchmark.py; pp_schedule_compare's prediction model is
asserted against its own measured output in its docstring run.)
"""

from __future__ import annotations

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


@pytest.mark.slow
def test_verify_weights_synthetic_self_test(capsys):
    from tools.verify_weights import synthetic_self_test

    assert synthetic_self_test()
    out = capsys.readouterr().out
    assert "forward: PASS" in out
    assert "backward: PASS" in out
    assert "RESULT: OK" in out


def test_profile_flops_breakdown_matches_mfu_formula():
    from scaletorch_tpu.models.presets import preset
    from tools.profile_mfu import flops_breakdown

    p = preset("qwen3-0.6b")
    seq = 8192
    br = flops_breakdown(p, seq)
    assert br["forward"] == br["linear"] + br["attention"] + br["embed_head"]
    # attention term matches the shared MFU formula's 12*L*heads*hd*seq
    # (utils/misc.get_mfu): 3x the forward 4*L*heads*hd*seq
    assert 3 * br["attention"] == 12 * p["num_hidden_layers"] * \
        p["num_attention_heads"] * p["head_dim"] * seq


def test_group_hosts_slice_major_ranks():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "group_hosts", os.path.join(REPO, "scripts", "group_hosts.py"))
    gh = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gh)

    lines = [
        "t1v-n-abc-w-0",          # slice t1v-n-abc
        "10.0.0.1 rack-b",        # explicit rack column
        "t1v-n-abc-w-1",
        "10.0.0.2 rack-b",
        "bare-host",              # its own group
    ]
    groups = gh.group_hosts(lines)
    assert groups["t1v-n-abc"] == ["t1v-n-abc-w-0", "t1v-n-abc-w-1"]
    assert groups["rack-b"] == ["10.0.0.1", "10.0.0.2"]
    assert groups["bare-host"] == ["bare-host"]
    # slice-major contiguous ranks: same slice -> adjacent process indices
    ranks = gh.rank_assignment(groups)
    by_key = {}
    for rank, _, key in ranks:
        by_key.setdefault(key, []).append(rank)
    for key, rs in by_key.items():
        assert rs == list(range(rs[0], rs[0] + len(rs))), (key, rs)
    # rendered output round-trips through the grouped-file parser
    assert gh.group_hosts(gh.render(groups).splitlines()) == groups


def test_optimize_mfu_gen_detection():
    """The AOT prefilter's HBM budget must track the actual chip: the
    device-kind -> generation mapping is a pure function, tested here."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "omfu", os.path.join(REPO, "tools", "optimize_mfu.py"))
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    # explicit flag always wins
    assert m._detect_gen("v5p") == "v5p"
    assert m._detect_gen("v6e") == "v6e"
    assert m._GEN_BY_KIND["TPU v5 lite"] == "v5e"
    # a device it does not know (here: the CPU) is an error, never a
    # default generation's HBM budget
    with pytest.raises(SystemExit, match="device_kind 'cpu'"):
        m._detect_gen(None)


TIMING_TOOLS = [
    "bench_single.py", "bench_cp_compare.py",
    "bench_moe_dispatch.py", "pp_schedule_compare.py", "optimize_mfu.py",
    "profile_mfu.py",
]


@pytest.mark.parametrize("tool", TIMING_TOOLS)
def test_timing_tool_refuses_to_run_without_a_tpu(tool):
    """Every tool that prints a time, a rate or an MFU fails — non-zero,
    naming the platform it found — where jax has no TPU. (Their CPU
    modes are gone: that dispatch-vs-einsum and the CP strategies agree
    on the loss is asserted in tests/parallel, not by timing them.)"""
    import subprocess
    import sys as _sys

    r = subprocess.run(
        [_sys.executable, os.path.join(REPO, "tools", tool)],
        capture_output=True, text=True, timeout=300, cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert r.returncode != 0
    assert "needs a TPU: jax found platform 'cpu'" in r.stderr, \
        r.stderr[-2000:]
    assert "tok/s" not in r.stdout and "MFU" not in r.stdout, \
        r.stdout[-2000:]
