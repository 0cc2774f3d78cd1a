"""Where ``setup_s`` starts (PR 46): at the stamp ``run.py`` takes when
``jax.devices()`` has returned. What lies before it is the runtime's
bring-up (importing jax, opening the chip: 10-15 s on the v5e, by the
run and not by the tree, ledger PRs 38-44) and is printed apart as
``runtime_bringup_s``, held by nothing. Shown with a sleep on each side
of the stamp, in rehearsals of the toy training cell."""

import re
import textwrap

import pytest

from tests.benchmarks.helpers import CONTRACT_KEYS, run_cell
from tests.benchmarks.toy import REPO, make_toy_root

SEED = str(2**31 + 4601)
DELAY_S = 6.0
# what two rehearsals of one toy cell may differ by on a loaded CPU
SLACK_S = 3.0

WRAPPER = textwrap.dedent('''
    import sys
    import time

    sys.path.insert(0, {repo!r})
    import benchmarks.run as run

    if {where!r} == "before":
        import jax

        target, name = jax, "devices"
    else:
        from benchmarks.lib import device as target

        name = "describe"
    real = getattr(target, name)


    def slow(*args, **kwargs):
        # the first call alone: the runners ask for the devices again
        setattr(target, name, real)
        time.sleep({delay})
        return real(*args, **kwargs)


    setattr(target, name, slow)
    sys.exit(run.main())
''')


@pytest.fixture(scope="module")
def delayed(tmp_path_factory):
    """The same cell and seed twice: ``DELAY_S`` inside ``jax.devices()``
    (before the stamp), then inside the first call after it."""
    tmp = tmp_path_factory.mktemp("stamp")
    root = make_toy_root(str(tmp / "toy"))
    runs = {}
    for where in ("before", "after"):
        script = tmp / f"sleep_{where}.py"
        script.write_text(WRAPPER.format(repo=REPO, where=where,
                                         delay=DELAY_S))
        runs[where] = run_cell(
            ["--root", root, "--workload", "toy-train", "--seed", SEED,
             "--seconds", "1", "--trace", "0", "--rehearse"],
            script=str(script))
    return runs


def numbers(run):
    """(set-up, bring-up, the ``[bench ...s]`` stamps by their first
    word): the stamps count from the process's start."""
    rc, line, out = run
    assert rc == 3 and line["correct"] is True, out
    stamps = {m.group(2).split("=")[0].split(":")[0]: float(m.group(1))
              for m in re.finditer(r"\[bench\s+([0-9.]+)s\] (\S+)", out)}
    return (line["metrics"]["setup_s"]["value"], line["runtime_bringup_s"],
            stamps)


def test_a_delay_before_the_device_stamp_is_bring_up_not_set_up(delayed):
    setup, bringup, stamps = numbers(delayed["before"])
    assert bringup >= DELAY_S
    assert bringup - numbers(delayed["after"])[1] > DELAY_S - SLACK_S
    # set-up did not see it: it runs from the ``platform=`` line, which
    # stands after the sleep, to the line that opens the window (two
    # rehearsals' set-ups cannot be compared: each compiles on the CPU)
    assert stamps["platform"] >= DELAY_S
    assert setup == pytest.approx(stamps["warm"] - stamps["platform"],
                                  abs=0.5)


def test_a_delay_after_the_device_stamp_is_set_up(delayed):
    setup, bringup, stamps = numbers(delayed["after"])
    assert setup >= DELAY_S
    assert setup == pytest.approx(stamps["warm"] - bringup, abs=0.5)
    # the ``platform=`` line is printed after the sleep, the stamp was
    # taken before it
    assert stamps["platform"] - bringup >= DELAY_S - 0.1


@pytest.mark.parametrize("where", ["before", "after"])
def test_the_line_has_both_and_they_add_up_to_the_old_reading(delayed,
                                                              where):
    """``runtime_bringup_s`` is a key of the line, no metric (nothing
    holds it); with ``setup_s`` it adds up to what ``setup_s`` read
    until PR 46, process start to window open, which the log's stamps
    still count."""
    rc, line, out = delayed[where]
    assert CONTRACT_KEYS <= set(line)
    assert "runtime_bringup_s" not in line["metrics"]
    assert list(line)[-2:] == ["check", "problems"]
    setup, bringup, stamps = numbers(delayed[where])
    assert bringup > 0
    assert bringup + setup == pytest.approx(stamps["warm"], abs=0.5)
