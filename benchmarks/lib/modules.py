"""Code that a data file names by its path.

A configuration names its plain reference (``"reference": "<path>.py"``),
a ``roofline_share`` reader or a configuration's ``cost_inputs`` may name
a cost module. Each is a file under the benchmark's own directories,
imported here by that path, once a process; nothing in ``benchmarks/lib``
imports one by name, so a new family or a new kernel brings its own file
and edits none.
"""

from __future__ import annotations

import importlib.util
import re
import sys
from typing import Any, Callable, Dict, Optional

from benchmarks.lib.spec import Refused, Spec

# what the two runners call on a reference module, and nothing more
REFERENCE_CONTRACT = ("make_loss_fn", "make_logits_fn", "GAIN_KEYS")
DEFAULT_COST_MODULE = "benchmarks/lib/costs.py"


def load(spec: Spec, relative: str, wanted_by: str):
    """The module at ``relative`` (``Spec.find``), imported by its path."""
    path = spec.find(relative)
    if path is None:
        raise Refused(f"{wanted_by} names {relative!r}: no such file under "
                      f"{spec.root} or the checkout")
    name = "_bench_" + re.sub(r"\W", "_", path)
    if name not in sys.modules:
        module_spec = importlib.util.spec_from_file_location(name, path)
        module = importlib.util.module_from_spec(module_spec)
        sys.modules[name] = module
        try:
            module_spec.loader.exec_module(module)
        except BaseException:
            del sys.modules[name]
            raise
    return sys.modules[name]


def reference_of(spec: Spec, config: Dict[str, Any]):
    """The plain reference a configuration names, held to its contract."""
    who = f"configuration {config.get('name', '?')!r}"
    relative = config.get("reference")
    if not relative:
        raise Refused(f"{who} names no 'reference': the path of its plain "
                      "reference module")
    module = load(spec, relative, who)
    missing = [n for n in REFERENCE_CONTRACT if not hasattr(module, n)]
    if missing:
        raise Refused(f"{who}: its reference {relative!r} lacks "
                      f"{', '.join(missing)}")
    return module


def check_sizes(check: Dict[str, Any], runners_own) -> Dict[str, Any]:
    """The keys of a cell's ``check`` that are not the runner's own:
    sizes of the reference, handed to its factory as they are."""
    return {k: v for k, v in check.items() if k not in runners_own}


def cost_function(spec: Optional[Spec], function: str,
                  relative: Optional[str] = None) -> Callable:
    """``function`` of the cost module at ``relative``; of
    ``benchmarks/lib/costs.py`` where none is named."""
    if relative in (None, DEFAULT_COST_MODULE):
        from benchmarks.lib import costs as module
    else:
        module = load(spec, relative, f"cost function {function!r}")
    if not hasattr(module, function):
        raise Refused(f"cost module {relative or DEFAULT_COST_MODULE!r} "
                      f"has no function {function!r}")
    return getattr(module, function)
