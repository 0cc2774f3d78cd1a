"""The engine's oracle: greedy decoding by the model's PLAIN forward.

No cache of any kind and none of the engine's code: every token is the
argmax of the full-sequence training forward (``llama.forward`` /
``qwen3_moe.forward`` / ``gpt_moe.forward`` / ``afmoe.forward`` /
``olmo_hybrid.forward``, ``qwen3_next.forward``, ``kimi_linear.forward``,
``granite_moe_hybrid.forward`` and ``jamba.forward`` with the recurrence
row after row) over the
whole sequence so far. The engine's page pool, page tables, prefix sharing, cached
forwards and sampling are all on the other side of the comparison.

On the CPU in float32 at the tiny presets the tokens are equal. Should
two logits ever tie within float32 summation order, ``assert_greedy``
accepts the engine's token only if its logit is within ``TIE_RTOL`` of
the largest |logit| from the oracle's maximum, and goes on from the
engine's token.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from scaletorch_tpu.models import (
    granite_moe_hybrid,
    jamba,
    kimi_linear,
    olmo_hybrid,
    qwen3_next,
)
from scaletorch_tpu.models.families import family_of

# of the largest |logit| of the step: float32 order-of-summation noise
# measured on these presets is under 1e-6; a wrong token is off by 1e-2
TIE_RTOL = 1e-5

# the oracle's own: a recurrence as its definition, row after row, not
# the chunked form the engine's prefill runs
AS_ITS_DEFINITION = {
    qwen3_next: {"sequential": True},
    olmo_hybrid: {"sequential": True},
    kimi_linear: {"sequential": True},
    granite_moe_hybrid: {"sequential": True},
    jamba: {"scan": "sequential"},
}


def plain_forward(cfg):
    """The full-sequence forward of a config's family (the training
    forward, not the cache-aware one)."""
    module = family_of(cfg).module
    return functools.partial(module.forward,
                             **AS_ITS_DEFINITION.get(module, {}))


@functools.lru_cache(maxsize=None)
def _jitted(cfg):
    fwd = plain_forward(cfg)
    return jax.jit(lambda params, tokens: fwd(params, tokens, cfg))


def last_logits(params, cfg, seq, *, pad_to=32):
    """float32 logits [V] after ``seq``, from one plain forward over the
    whole sequence. It sits at the head of a buffer zero-padded to a
    multiple of ``pad_to`` (one compile per config and per 32 lengths,
    not per length): under the causal mask the row read never sees the
    padding."""
    buf = np.zeros((1, -(-max(len(seq), 1) // pad_to) * pad_to), np.int32)
    buf[0, :len(seq)] = seq
    logits = _jitted(cfg)(params, jnp.asarray(buf))
    return np.asarray(logits[0, len(seq) - 1], np.float32)


def greedy_by_forward(params, cfg, prompt, n):
    """``n`` greedy tokens after ``prompt``: the plain forward on the
    whole sequence each step, argmax."""
    seq = list(prompt)
    for _ in range(n):
        seq.append(int(np.argmax(last_logits(params, cfg, seq))))
    return seq[len(prompt):]


def sampled_by_forward(params, cfg, prompt, n, *, seed, sampling):
    """``n`` sampled tokens after ``prompt``: the plain forward on the
    whole sequence each step, and the engine's rule for the key of a
    token: the request's seed folded with the position of the token fed
    last (``sampling.slot_keys``)."""
    from scaletorch_tpu.inference.sampling import sample_one

    base = jax.random.PRNGKey(seed)
    seq = list(prompt)
    for _ in range(n):
        key = jax.random.fold_in(base, len(seq) - 1)
        logits = jnp.asarray(last_logits(params, cfg, seq))
        seq.append(int(sample_one(logits, key, sampling)))
    return seq[len(prompt):]


def assert_greedy(params, cfg, prompt, tokens):
    """``tokens`` is the greedy continuation of ``prompt`` by the plain
    forward (module docstring for what a tie may do)."""
    seq = list(prompt)
    for i, token in enumerate(tokens):
        logits = last_logits(params, cfg, seq)
        best = int(np.argmax(logits))
        if token != best:
            gap = float(logits[best] - logits[token])
            scale = float(np.max(np.abs(logits)))
            assert gap <= TIE_RTOL * scale, (
                f"token {i}: engine {token} (logit {logits[token]}), "
                f"oracle {best} (logit {logits[best]})")
        seq.append(token)
