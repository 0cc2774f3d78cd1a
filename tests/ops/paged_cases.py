"""The poisoned pool the interpret-mode cases of the paged-decode kernel
run over (``test_paged_decode_blocks.py``, ``test_paged_decode_chain.py``)
and the two compiled calls they make, the kernel's and the fallback's:
not collected, imported by both.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from scaletorch_tpu.models.layers import cached_sdpa_attention
from scaletorch_tpu.ops.pallas.paged_attention import (
    TRASH_PAGE,
    paged_gather_kv,
    pallas_paged_decode_attention,
)


@functools.lru_cache(maxsize=None)
def interpreted_decode(window=None):
    """The kernel in interpret mode as ONE jitted call a ``window``: jit
    keeps a program a shape, so cases that differ in their operands
    alone (positions, tables, what the pool holds) trace, lower and
    compile the interpreted kernel once, 2-3 s of a case's 3."""
    return jax.jit(functools.partial(
        pallas_paged_decode_attention, interpret=True, window=window))


@functools.partial(jax.jit, static_argnames="window")
def _fallback(q, pool_k, pool_v, tables, pos, window):
    """The gather fallback's answer, compiled: run operation by
    operation it is thirty small compiles a shape, 0.9 s a case."""
    return cached_sdpa_attention(
        q[:, :, None], paged_gather_kv(pool_k, tables),
        paged_gather_kv(pool_v, tables), pos[:, None],
        window=window)[:, :, 0]


def poisoned_case(hkv, n_rep, d, page_size, max_pages, pos, *, window=None,
                  shared=None, seed=0):
    """One slot a position of ``pos`` (-1: a slot with no key) over a
    float32 pool whose TRASH page and every page no live key sits on are
    all NaN; a table holds TRASH or such a page wherever the walk does
    not go (past the live length, and before a ``window``'s first page).
    ``shared`` (i, j): slot j's first two pages are slot i's. Returns
    the kernel's inputs and the fallback's answer from the same pool
    with the NaN zeroed."""
    rng = np.random.default_rng(seed)
    pos = np.asarray(pos)
    b = len(pos)
    n_live = np.clip(pos // page_size + 1, 0, max_pages)
    first = np.zeros(b, int) if window is None else \
        np.maximum(pos - window + 1, 0) // page_size
    n_pages = b * max_pages + 1
    tables = rng.permutation(np.arange(1, n_pages)).reshape(b, max_pages)
    if shared is not None:
        tables[shared[1], :2] = tables[shared[0], :2]
    live = np.zeros(n_pages, bool)
    for row, f, n in zip(tables, first, n_live):
        live[row[f:n]] = True
    for row, f, n in zip(tables, first, n_live):
        off = np.r_[0:f, n:max_pages]
        row[off] = np.where(rng.random(len(off)) < 0.5, TRASH_PAGE,
                            rng.choice(np.flatnonzero(~live), len(off)))
    shape = (n_pages, hkv, page_size, d)
    pool_k = rng.standard_normal(shape, np.float32)
    pool_v = rng.standard_normal(shape, np.float32)
    q = jnp.asarray(rng.standard_normal((b, hkv * n_rep, d), np.float32))
    tables = jnp.asarray(tables, jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)
    want = _fallback(q, jnp.asarray(pool_k), jnp.asarray(pool_v), tables,
                     pos, window)
    # poisoned copies: jnp.asarray may alias the numpy buffer the
    # oracle above is still reading
    dead = ~live[:, None, None, None]
    return (q, jnp.asarray(np.where(dead, np.nan, pool_k)),
            jnp.asarray(np.where(dead, np.nan, pool_v)), tables, pos), \
        np.asarray(want)
