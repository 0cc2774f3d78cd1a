"""Runs ``benchmarks/run.py`` as the driver does, in a child process."""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
from typing import Any, Dict, List, Tuple

from tests.benchmarks.toy import REPO

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
CHAT_CELL = "serve-1.7b-chat"


def chat_held_metric(spec) -> Dict[str, Any]:
    """The one gap percentile among the chat cell's end-to-end metrics:
    which one is the measurement's to decide (benchmarks/README.md,
    "Which percentile"), so the tests read it and do not name it."""
    (held,) = [m for m in spec.end_to_end(CHAT_CELL)
               if re.fullmatch(r"serve_itl_p\d+_ms", m["name"])]
    return held


def run_cell(args: List[str], *, cwd: str = REPO, script: str = None,
             xla_flags: str = None, timeout: float = 600.0,
             ) -> Tuple[int, Dict[str, Any], str]:
    """(exit code, the last stdout line parsed or {}, output's end)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # the child picks its own device count
    if xla_flags:
        env["XLA_FLAGS"] = xla_flags
    script = script or os.path.join(REPO, "benchmarks", "run.py")
    proc = subprocess.run([sys.executable, script, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)
    lines = [l for l in proc.stdout.splitlines() if l.strip()]
    last: Dict[str, Any] = {}
    if lines and lines[-1].startswith("{"):
        last = json.loads(lines[-1])
    return proc.returncode, last, proc.stdout[-3000:] + proc.stderr[-3000:]
