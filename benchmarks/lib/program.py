"""The program's own way from a configuration file to a model.

The harness passes the configuration through and the program decides
what it is: every top-level key of the file that the program's launch
arguments declare goes to them under the same name, and the model
configuration (and, for serving, its initialiser) comes from the
program's own dispatch on those arguments.
"""

from __future__ import annotations

import dataclasses
import sys
from typing import Any, Callable, Dict


def model_arguments(config: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration's top-level keys that the program's arguments
    declare as fields (the published ``config.json`` names it takes)."""
    from scaletorch_tpu.config import ScaleTorchTPUArguments

    declared = {f.name for f in dataclasses.fields(ScaleTorchTPUArguments)}
    return {k: v for k, v in config.items() if k in declared}


def launch_arguments(config: Dict[str, Any], **launch: Any):
    """``ScaleTorchTPUArguments`` of a configuration plus launch values."""
    from scaletorch_tpu.config import ScaleTorchTPUArguments

    return ScaleTorchTPUArguments(**{**model_arguments(config), **launch})


def serving_model(config: Dict[str, Any], dtype_name: str):
    """(model config, its module's ``init_params``) as the program
    builds them for this configuration, weights and compute both in the
    dtype the model is served in."""
    from scaletorch_tpu.trainer.trainer import build_model_config

    cfg = build_model_config(launch_arguments(
        config, dtype=dtype_name, param_dtype=dtype_name))
    init: Callable = sys.modules[type(cfg).__module__].init_params
    return cfg, init
