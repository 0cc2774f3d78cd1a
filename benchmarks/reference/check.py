"""The comparison that decides ``correct``, with its tolerances.

Every tolerance below was set from errors measured on the chip over
seeds that are not the defaults of anything (PERF.md, Findings, PR 23
lists each seed's error), as a stated multiple of the largest, and each
is shown there to reject a deliberately wrong computation
(``reference/qwen3.py`` ``wrong=``). What the errors are made of: the
system computes in bf16 (8 bits of mantissa, 2**-9 relative rounding
per operation) with float32 accumulation, the reference in float32 at
``highest`` precision; the two are otherwise the same mathematics.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import numpy as np

# Training, first step (parameters as initialised), Qwen3-0.6B at 8192
# tokens on the v5e, twenty-nine seeds (PERF.md, Findings, PR 23).
# The loss is a mean over thousands of positions of a log-sum-exp over
# 151,936 logits, so bf16 rounding averages out: |system - reference| /
# reference was 7.9e-8 .. 1.51e-5. The tolerance is 4x the largest. At
# random init the loss is ln V + a little whatever attention does: this
# line catches a loss summed where it should be averaged, a shard left
# out of it, a missing norm (8.9e-5); it does not see attention.
TRAIN_LOSS_RTOL = 6e-5
# The global gradient norm sums squares over 0.6e9 gradients computed in
# bf16: relative difference 4.9e-5 .. 2.25e-3 over the same seeds, with
# a tail (one seed in twenty-nine above 1.5e-3). 4x the largest. It
# catches what changes the scale of the backward pass (no q/k norm:
# 2.5e-2) and does not see attention either (a lost ring hop: 1.1e-3).
TRAIN_GRAD_NORM_RTOL = 9e-3
# The part that sees attention: the gradient of every norm gain (q_norm,
# k_norm and the two layer norms of 28 layers, the final norm; 0.07 M
# numbers, read back from Adam's first moment after the first step),
# system against reference as |difference| / |reference| per kind of
# gain, the largest kind judged. Each of these gradients sums over all
# positions what attention sent back, so hiding a block of keys from
# some queries moves them by tens of per cent. Measured at 8192 tokens:
# the system 1.85e-2 .. 2.36e-2 over sixteen seeds (q_norm or k_norm
# the largest kind; bf16 everywhere, steady from seed to seed); the
# reference with one ring hop lost 0.187, 0.199 and 0.240; with
# attention in bf16 6.2e-3 .. 7.3e-3 (inside, as it must be: the system
# computes so). 3x the largest, which the lost hop passes 2.7 to 3.4
# times.
TRAIN_GAIN_GRAD_RTOL = 7e-2
# Serving logits, Qwen3-1.7B in bf16: the largest |system - reference|
# over every compared logit (prefill's last row and 64 decoded positions
# of 8 prompts of 16..1024 tokens), as a share of the largest |reference
# logit| of the run: 1.07e-2 .. 1.27e-2 over eight seeds. 3x the largest.
SERVE_LOGITS_RTOL_OF_MAX = 3.8e-2


def relative(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


def relative_l2(a, b) -> float:
    """``|a - b| / |b|`` over all elements, in float64."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-300))


def judge_train(system: Dict[str, Any], reference: Dict[str, Any],
                *, loss_rtol: float = TRAIN_LOSS_RTOL,
                grad_norm_rtol: float = TRAIN_GRAD_NORM_RTOL,
                gain_grad_rtol: float = TRAIN_GAIN_GRAD_RTOL,
                ) -> Dict[str, Any]:
    """``system``/``reference``: ``loss`` and, optionally,
    ``grad_norm`` and ``gain_grads`` (name -> array: the gradient of
    each kind of norm gain, stacked over layers) of the first step.
    Returns the errors and ``ok``."""
    out: Dict[str, Any] = {
        "loss_system": system["loss"], "loss_reference": reference["loss"],
        "loss_rel_err": relative(system["loss"], reference["loss"]),
        "loss_rtol": loss_rtol,
        "grad_norm_system": system.get("grad_norm"),
    }
    ok = math.isfinite(system["loss"]) and out["loss_rel_err"] <= loss_rtol
    if reference.get("grad_norm") is not None:
        out.update({
            "grad_norm_reference": reference["grad_norm"],
            "grad_norm_rel_err": relative(system["grad_norm"],
                                          reference["grad_norm"]),
            "grad_norm_rtol": grad_norm_rtol,
        })
        ok = ok and math.isfinite(system["grad_norm"]) \
            and out["grad_norm_rel_err"] <= grad_norm_rtol
    if reference.get("gain_grads") is not None:
        errs = {k: relative_l2(system["gain_grads"][k], v)
                for k, v in reference["gain_grads"].items()}
        out.update({"gain_grad_rel_l2": errs,
                    "gain_grad_rel_err": max(errs.values()),
                    "gain_grad_rtol": gain_grad_rtol})
        ok = ok and math.isfinite(out["gain_grad_rel_err"]) \
            and out["gain_grad_rel_err"] <= gain_grad_rtol
    out["ok"] = bool(ok)
    return out


def judge_logits(max_abs_err: float, max_abs_reference: float, *,
                 rtol_of_max: float = SERVE_LOGITS_RTOL_OF_MAX,
                 ) -> Dict[str, Any]:
    err = max_abs_err / max(max_abs_reference, 1e-30)
    return {"max_abs_err": max_abs_err,
            "max_abs_reference": max_abs_reference,
            "err_of_max": err, "rtol_of_max": rtol_of_max,
            "ok": bool(math.isfinite(max_abs_err) and err <= rtol_of_max)}
