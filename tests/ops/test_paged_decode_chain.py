"""The paged-decode kernel's walk from one slot into the next, in
interpret mode against the gather fallback over a poisoned pool
(``paged_cases.poisoned_case``), and the counter the engine keeps of it.
A file of its own (ROADMAP D1): ``--dist loadfile`` gives a file to one
worker, and these cases beside the rest of
``tests/inference/test_paged_cache.py`` were that worker's whole run.
Quick tier, CPU.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from scaletorch_tpu.ops.pallas.paged_attention import (
    TRASH_PAGE,
    _next_live_slot,
    _pages_per_block,
    _slot_walk,
    chained_first_blocks,
    pallas_paged_decode_attention,
)
from tests.ops.paged_cases import interpreted_decode, poisoned_case


def _plain_chain(n_live):
    """The walk one slot after another, as the kernel makes it: (slots
    walked, slots that found their first block started, for each slot
    the slot whose first block it starts or None)."""
    walked = chained = 0
    in_flight = False
    starts = []
    for b, n in enumerate(n_live):
        if n <= 0:
            starts.append(None)
            continue
        walked += 1
        chained += in_flight
        later = [s for s in range(b + 1, len(n_live)) if n_live[s] > 0]
        starts.append(later[0] if later else None)
        in_flight = bool(later)
    return walked, chained, starts


class TestDecodeKernelChain:
    """The copy pipeline does not stop at a slot's end: behind its last
    block a slot starts block 0 of the next slot that has a live page,
    in the other landing buffer, and that slot does not start it again.
    Parity with the fallback wherever the hand-over can go wrong: block
    edges, dead slots looked past, the buffer parity after an odd walk,
    a window's walk from mid-table; every page a walk must not touch is
    NaN (``poisoned_case``)."""

    HKV = 2
    PATTERNS = ["block-edges", "dead-slots", "one-live-slot",
                "one-block-then-many", "window"]

    @staticmethod
    def _positions(pattern, bk, page_size):
        """(positions, window, pages a table holds) of one pattern, in
        keys ``bk`` a block."""
        window = None
        if pattern == "block-edges":    # ends on, short of, past an edge
            pos = [bk - 1, bk - 2, bk, 2 * bk - 1, 2 * bk - 2, 2 * bk]
        elif pattern == "dead-slots":   # first, alone between, two, last
            pos = [-1, bk + 3, -1, 5, -1, -1, 2 * bk, -1]
        elif pattern == "one-live-slot":
            pos = [-1, -1, bk + 1, -1]
        elif pattern == "one-block-then-many":   # 1, 2, 3, 1, 3, 1 blocks
            pos = [3, 2 * bk - 1, 3 * bk - 5, 5, 2 * bk + 1, 9]
        else:   # "window": up to two blocks of a walk from mid-table
            window = bk + page_size + 3
            pos = [2, window - 1, window, 2 * bk + 5, -1, 3 * bk - 3]
        return pos, window, 3 * bk // page_size + 1

    @pytest.mark.parametrize("pattern", PATTERNS)
    @pytest.mark.parametrize("page_size", [8, 16])
    @pytest.mark.parametrize("d", [128, 256])
    @pytest.mark.parametrize("n_rep", [1, 2, 8])
    def test_matches_fallback(self, n_rep, d, page_size, pattern):
        ppb = _pages_per_block(page_size, self.HKV, d, jnp.float32, 10 ** 6)
        pos, window, max_pages = self._positions(
            pattern, ppb * page_size, page_size)
        args, want = poisoned_case(self.HKV, n_rep, d, page_size, max_pages,
                                    pos, window=window)
        assert bool(jnp.isnan(args[1][TRASH_PAGE]).all())
        out = np.asarray(interpreted_decode(window)(*args))
        live = np.asarray(pos) >= 0
        assert np.isfinite(out).all()
        np.testing.assert_allclose(out[live], want[live], atol=5e-6)
        assert (out[~live] == 0).all()

    @pytest.mark.parametrize("pattern", PATTERNS)
    def test_every_started_copy_is_waited_for_once(self, pattern):
        """Under the TPU interpreter a copy happens when it is WAITED
        for, semaphores count and a buffer never written reads NaN: a
        first block nobody started would wait for ever, one that landed
        in the wrong buffer would reduce NaN."""
        from jax.experimental.pallas import tpu as pltpu

        pos, window, max_pages = self._positions(pattern, 128, 16)
        args, want = poisoned_case(self.HKV, 2, 128, 16, max_pages, pos,
                                    window=window)
        out = np.asarray(pallas_paged_decode_attention(
            *args, window=window, interpret=pltpu.InterpretParams(
                dma_execution_mode="on_wait", uninitialized_memory="nan")))
        live = np.asarray(pos) >= 0
        np.testing.assert_allclose(out[live], want[live], atol=5e-6)

    @pytest.mark.parametrize("window", [None, 40], ids=["full", "window"])
    @pytest.mark.parametrize("pos", [
        [5, 17, 200, 31], [-1, 5, -1, -1, 40, -1], [-1, -1, -1], [7],
        [-1, 300, 9999, 0],
    ], ids=["all-live", "dead-between", "all-dead", "one", "past-table"])
    def test_the_counter_is_the_kernel_s_rule(self, pos, window):
        """``chained_first_blocks`` (what the engine counts) against the
        walk one slot after another, and the kernel's own search for the
        slot it hands its pipeline to against the same walk."""
        page_size, max_pages = 8, 32
        _, n_live = _slot_walk(np.asarray(pos), page_size, max_pages, window,
                               xp=np)
        walked, chained, starts = _plain_chain(n_live)
        assert chained_first_blocks(
            pos, page_size, max_pages, window) == (walked, chained)
        pos_ref, n = jnp.asarray(pos, jnp.int32), len(pos)
        for b, want in enumerate(starts):
            if n_live[b] > 0:
                got = int(_next_live_slot(
                    pos_ref, b, n,
                    lambda p: _slot_walk(p, page_size, max_pages, window)[1]))
                assert got == (n if want is None else want)
