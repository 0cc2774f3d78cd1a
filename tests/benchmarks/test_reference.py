"""The plain float32 reference and the comparison that decides
``correct``: its explicit backward pass against ``jax.grad``, and the
deliberately wrong variants against the tolerances."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import check
from benchmarks.reference import qwen3 as ref
from tests.benchmarks.toy import TOY_MODEL

SEQ = 64
D = ref.dims(TOY_MODEL)


def toy_params(seed, scale=0.1):
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))
    L, H, V, f, hd = (D["layers"], D["hidden"], D["vocab"], D["ffn"],
                      D["head_dim"])
    q, kv = D["heads"] * hd, D["kv_heads"] * hd

    def n(shape):
        return scale * jax.random.normal(next(keys), shape)

    return {"embed_tokens": n((V, H)), "norm": 1 + n((H,)), "layers": {
        "input_layernorm": 1 + n((L, H)), "q_proj": n((L, H, q)),
        "k_proj": n((L, H, kv)), "v_proj": n((L, H, kv)),
        "o_proj": n((L, q, H)), "post_attention_layernorm": 1 + n((L, H)),
        "gate_proj": n((L, H, f)), "up_proj": n((L, H, f)),
        "down_proj": n((L, f, H)), "q_norm": 1 + n((L, hd)),
        "k_norm": 1 + n((L, hd))}}


def toy_batch(seed):
    toks = np.random.default_rng(seed).integers(0, D["vocab"], SEQ + 1)
    return (jnp.asarray(toks[:-1]), jnp.asarray(toks[1:]),
            jnp.arange(SEQ, dtype=jnp.int32))


@pytest.fixture(scope="module")
def truth():
    """Loss and gradient norm by ``jax.grad`` of the plain loss."""
    params = toy_params(0)
    batch = toy_batch(0)
    fn = functools.partial(ref.loss, d=D, q_block=16, loss_chunk=16)
    value, grads = jax.value_and_grad(fn)(params, *batch)
    norm = jnp.sqrt(sum(jnp.sum(g ** 2) for g in jax.tree.leaves(grads)))
    gains = {k: np.asarray(grads["layers"][k]) for k in ref.GAIN_KEYS}
    gains["norm"] = np.asarray(grads["norm"])
    return {"params": params, "batch": batch, "loss": float(value),
            "grad_norm": float(norm), "gain_grads": gains}


def gains_of(tree):
    return dict({k: np.asarray(v) for k, v in tree["layers"].items()},
                norm=np.asarray(tree["norm"]))


def test_explicit_backward_pass_equals_jax_grad(truth):
    fn = ref.make_loss_fn(TOY_MODEL, q_block=16, loss_chunk=16,
                          with_gradients=True)
    value, norm, gains = fn(truth["params"], *truth["batch"])
    assert float(value) == pytest.approx(truth["loss"], rel=1e-6)
    assert float(norm) == pytest.approx(truth["grad_norm"], rel=1e-5)
    gains = gains_of(gains)
    assert set(gains) == set(ref.GAIN_KEYS) | {"norm"}
    for name, want in truth["gain_grads"].items():
        assert gains[name].shape == want.shape
        assert check.relative_l2(gains[name], want) < 1e-5, name


@pytest.mark.parametrize("q_block,loss_chunk", [(8, 8), (32, 64), (64, 16)])
def test_block_and_chunk_sizes_do_not_change_the_result(truth, q_block,
                                                        loss_chunk):
    fn = ref.make_loss_fn(TOY_MODEL, q_block=q_block, loss_chunk=loss_chunk)
    assert float(fn(truth["params"], *truth["batch"])) == pytest.approx(
        truth["loss"], rel=1e-6)


def test_attention_is_causal(truth):
    """Changing a later token leaves earlier logits alone."""
    tokens, _, _ = truth["batch"]
    fn = ref.make_logits_fn(TOY_MODEL, q_block=16)
    rows = jnp.arange(8, dtype=jnp.int32)[None]
    a = fn(truth["params"], tokens[None], rows)
    b = fn(truth["params"], tokens.at[40].set(3)[None], rows)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    rows_late = jnp.asarray([[41, 63]], jnp.int32)
    c = fn(truth["params"], tokens[None], rows_late)
    d = fn(truth["params"], tokens.at[40].set(3)[None], rows_late)
    assert float(jnp.max(jnp.abs(c - d))) > 1e-4


def test_zigzag_order_is_transparent_through_positions(truth):
    """Rows permuted together with their positions: same loss (what
    makes the cp layout invisible to the reference)."""
    tokens, targets, positions = truth["batch"]
    perm = np.random.default_rng(1).permutation(SEQ)
    fn = ref.make_loss_fn(TOY_MODEL, q_block=16, loss_chunk=16)
    got = fn(truth["params"], tokens[perm], targets[perm], positions[perm])
    assert float(got) == pytest.approx(truth["loss"], rel=1e-6)


@pytest.mark.parametrize("wrong", ["bf16_attention", "drop_block",
                                   "no_qk_norm"])
def test_wrong_variants_fail_the_train_tolerance(truth, wrong):
    """The tolerances of ``check.py`` (set on the chip at real size)
    reject each wrong computation at toy size too."""
    fn = ref.make_loss_fn(TOY_MODEL, q_block=16, loss_chunk=16, wrong=wrong,
                          with_gradients=True)
    value, norm, gains = fn(truth["params"], *truth["batch"])
    verdict = check.judge_train(
        {"loss": float(value), "grad_norm": float(norm),
         "gain_grads": gains_of(gains)}, truth)
    assert not verdict["ok"], verdict
    if wrong != "bf16_attention":
        # the part of the check that sees attention, with room to spare
        assert verdict["gain_grad_rel_err"] > 3 * verdict["gain_grad_rtol"]


@pytest.mark.parametrize("wrong", ["drop_block", "no_qk_norm"])
def test_wrong_variants_fail_the_logits_tolerance(truth, wrong):
    """(bf16 attention is not among them: the server computes in bf16
    throughout, so that variant is inside the system's own error.)"""
    tokens, _, _ = truth["batch"]
    rows = jnp.arange(SEQ - 16, SEQ, dtype=jnp.int32)[None]
    good = ref.make_logits_fn(TOY_MODEL, q_block=16)(
        truth["params"], tokens[None], rows)
    bad = ref.make_logits_fn(TOY_MODEL, q_block=16, wrong=wrong)(
        truth["params"], tokens[None], rows)
    verdict = check.judge_logits(float(jnp.max(jnp.abs(bad - good))),
                                 float(jnp.max(jnp.abs(good))))
    assert not verdict["ok"], verdict


def test_the_right_computation_passes_both(truth):
    near = {k: v * (1 + 1e-3) for k, v in truth["gain_grads"].items()}
    assert check.judge_train(
        {"loss": truth["loss"] * (1 + 2e-5),
         "grad_norm": truth["grad_norm"] * (1 - 1e-3), "gain_grads": near},
        truth)["ok"]
    far = dict(near, k_norm=near["k_norm"] * 1.5)
    assert not check.judge_train(dict(truth, gain_grads=far), truth)["ok"]
    assert check.judge_logits(0.01, 5.0)["ok"]
    assert not check.judge_logits(float("nan"), 5.0)["ok"]
    assert not check.judge_train({"loss": float("nan"), "grad_norm": 1.0},
                                 {"loss": 1.0, "grad_norm": 1.0})["ok"]
