"""Engine half of warm rejoin: export/import of frozen prefix pages.

The acceptance attestation: a recipient engine warmed with a donor's
prefix pages serves its FIRST shared-prefix request with a physical
prefix hit and bit-identical greedy output — with ``decode_compile_count
== 1`` on both ends (the import rides the existing jitted fill step; a
cache-shaped fill value is a new argument structure, not a retrace of
the audited decode/prefill entries). Conservation: donor refcounts never
move across an export; an aborted/partial import releases every
allocation it made; warmed pages are frozen-from-birth and evictable at
zero like any cached prefix. Quick tier, CPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scaletorch_tpu.inference import InferenceEngine, SamplingParams
from scaletorch_tpu.models import llama

TINY = dict(
    vocab_size=64, hidden_size=32, intermediate_size=64,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    dtype=jnp.float32,
)
GREEDY = SamplingParams(temperature=0.0)
SYS = [7, 7, 7, 7, 3, 3, 3, 3]  # two full pages at page_size=4


@pytest.fixture(scope="module")
def tiny_llama():
    cfg = llama.LlamaConfig(**TINY)
    return cfg, llama.init_params(jax.random.PRNGKey(0), cfg)


def make_engine(params, cfg, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_seq", 32)
    kw.setdefault("prefill_len", 12)
    kw.setdefault("sampling", GREEDY)
    kw.setdefault("page_size", 4)
    return InferenceEngine(params, cfg, **kw)


def warmed_donor(params, cfg):
    """A donor that served one request over SYS, registering its two
    prompt pages in the radix tree."""
    eng = make_engine(params, cfg)
    eng.submit(SYS + [1], max_new_tokens=4)
    eng.run()
    return eng


def export_all(donor):
    pmap = donor.export_prefix_map()
    pages = [p for chain in pmap["chains"] for p in chain["pages"]]
    _meta, contents = donor.export_prefix_pages(pages)
    chains = [(c["tokens"], c["pages"]) for c in pmap["chains"]]
    return pmap, chains, contents


class TestExport:
    def test_prefix_map_shape(self, tiny_llama):
        cfg, params = tiny_llama
        donor = warmed_donor(params, cfg)
        pmap = donor.export_prefix_map()
        assert pmap["page_size"] == 4
        assert pmap["dtype"] == str(donor.cache.k.dtype)
        chain = pmap["chains"][0]
        assert chain["tokens"] == SYS  # the full-page prefix only
        assert len(chain["pages"]) == 2
        for p in chain["pages"]:
            assert pmap["pages"][p]["frozen"] is True
        expected = tuple([donor.cache.k.shape[0]]
                         + list(donor.cache.k.shape[2:]))
        assert tuple(pmap["page_shape"]) == expected

    def test_export_leaves_donor_refcounts_untouched(self, tiny_llama):
        cfg, params = tiny_llama
        donor = warmed_donor(params, cfg)
        pmap = donor.export_prefix_map()
        pages = pmap["chains"][0]["pages"]
        before = {p: donor.allocator.refcount(p) for p in pages}
        _meta, contents = donor.export_prefix_pages(pages + [999])
        assert set(contents) == set(pages)  # unknown page: absent
        after = {p: donor.allocator.refcount(p) for p in pages}
        assert before == after
        donor.allocator.check_conservation()
        # the copy is the real page bytes
        nbytes = int(np.prod([donor.cache.k.shape[0]]
                             + list(donor.cache.k.shape[2:]))
                     * donor.cache.k.dtype.itemsize)
        for k_bytes, v_bytes in contents.values():
            assert len(k_bytes) == nbytes and len(v_bytes) == nbytes

    def test_engine_without_a_prefix_cache_has_no_map(self, tiny_llama):
        cfg, params = tiny_llama
        eng = make_engine(params, cfg, prefix_cache=False)
        eng.submit(SYS + [1], max_new_tokens=3)
        eng.run()
        pmap = eng.export_prefix_map()
        assert pmap["chains"] == [] and pmap["pages"] == {}
        assert pmap["page_size"] == 4
        assert eng.export_prefix_pages([1, 2])[1] == {}


class TestImportParity:
    def test_warmed_recipient_first_request_hits_and_matches(
            self, tiny_llama):
        """The tentpole attestation: import -> first shared-prefix
        request is a physical prefix hit with bit-identical output and
        no retrace on either end."""
        cfg, params = tiny_llama
        donor = warmed_donor(params, cfg)
        pmap, chains, contents = export_all(donor)

        recipient = make_engine(params, cfg)
        result = recipient.import_prefix_pages(
            chains, contents, dtype=pmap["dtype"],
            page_shape=pmap["page_shape"], page_size=pmap["page_size"])
        assert result["pages"] == 2
        assert result["chains"] == [SYS]
        snap = recipient.metrics.snapshot()
        assert snap["warm_pages_total"] == 2
        assert snap["prefix_pages"] == 2

        # FIRST recipient request rides the warmed pages
        rid = recipient.submit(SYS + [2], max_new_tokens=4)
        recipient.step()  # admission tick
        assert recipient.metrics.prefix_hits == 1
        assert recipient.metrics.prefill_tokens_saved == len(SYS)
        results = recipient.run()

        # bit parity against the donor serving the same request
        rid_d = donor.submit(SYS + [2], max_new_tokens=4)
        donor_results = donor.run()
        assert results[rid].tokens == donor_results[rid_d].tokens
        assert results[rid].outcome == "ok"

        # no retrace through export, import, or the warmed serve
        assert donor.decode_compile_count == 1
        assert recipient.decode_compile_count == 1
        recipient.allocator.check_conservation()
        donor.allocator.check_conservation()

    def test_warmed_pages_are_evictable_at_zero(self, tiny_llama):
        cfg, params = tiny_llama
        donor = warmed_donor(params, cfg)
        pmap, chains, contents = export_all(donor)
        recipient = make_engine(params, cfg)
        recipient.import_prefix_pages(
            chains, contents, dtype=pmap["dtype"],
            page_shape=pmap["page_shape"], page_size=pmap["page_size"])
        # the tree holds the ONLY reference: evicting it all returns
        # the pool to capacity (frozen-from-birth, evictable at zero)
        recipient.radix.evict(recipient.num_pages)
        assert recipient.allocator.free_count == \
            recipient.allocator.capacity
        recipient.allocator.check_conservation()

    def test_import_dedups_shared_donor_pages(self, tiny_llama):
        """Two chains sharing a donor page import it ONCE."""
        cfg, params = tiny_llama
        donor = make_engine(params, cfg)
        donor.submit(SYS + [1], max_new_tokens=4)
        donor.run()
        donor.submit(SYS[:4] + [9, 9, 9, 9, 2], max_new_tokens=4)
        donor.run()
        pmap, chains, contents = export_all(donor)
        assert len(chains) == 2  # shared first page, diverging second
        recipient = make_engine(params, cfg)
        result = recipient.import_prefix_pages(
            chains, contents, dtype=pmap["dtype"],
            page_shape=pmap["page_shape"], page_size=pmap["page_size"])
        assert result["pages"] == 3  # 2 + 2 chains, 1 shared page
        recipient.allocator.check_conservation()
        # both warmed chains are servable, still on one compile
        recipient.submit(SYS + [3], max_new_tokens=2)
        recipient.submit(SYS[:4] + [9, 9, 9, 9, 3], max_new_tokens=2)
        recipient.run()
        assert recipient.metrics.prefix_hits == 2
        assert donor.decode_compile_count == 1
        assert recipient.decode_compile_count == 1


class TestImportDegradation:
    def test_partial_contents_keep_valid_prefix(self, tiny_llama):
        """A dropped chunk sheds the chain's TAIL only — conservation
        holds on the recipient and the surviving prefix still hits."""
        cfg, params = tiny_llama
        donor = warmed_donor(params, cfg)
        pmap, chains, contents = export_all(donor)
        second_page = chains[0][1][1]
        del contents[second_page]  # the chunk that never arrived
        recipient = make_engine(params, cfg)
        result = recipient.import_prefix_pages(
            chains, contents, dtype=pmap["dtype"],
            page_shape=pmap["page_shape"], page_size=pmap["page_size"])
        assert result["pages"] == 1
        assert result["chains"] == [SYS[:4]]
        recipient.allocator.check_conservation()
        recipient.submit(SYS + [2], max_new_tokens=4)
        recipient.step()
        assert recipient.metrics.prefill_tokens_saved == 4
        recipient.run()
        recipient.allocator.check_conservation()
        assert recipient.decode_compile_count == 1

    def test_aborted_import_releases_every_allocation(self, tiny_llama):
        """An exception mid-import (the transfer interrupted between
        write and registration) must leave the allocator exactly where
        it started — the conservation oracle stays green."""
        cfg, params = tiny_llama
        donor = warmed_donor(params, cfg)
        pmap, chains, contents = export_all(donor)
        recipient = make_engine(params, cfg)
        free_before = recipient.allocator.free_count

        def boom(tokens, pages):
            raise RuntimeError("interrupted mid-registration")

        recipient.radix.insert = boom
        with pytest.raises(RuntimeError):
            recipient.import_prefix_pages(
                chains, contents, dtype=pmap["dtype"],
                page_shape=pmap["page_shape"],
                page_size=pmap["page_size"])
        recipient.allocator.check_conservation()
        assert recipient.allocator.free_count == free_before
        assert recipient.metrics.warm_pages_total == 0

    def test_incompatible_pool_is_refused(self, tiny_llama):
        cfg, params = tiny_llama
        donor = warmed_donor(params, cfg)
        pmap, chains, contents = export_all(donor)
        recipient = make_engine(params, cfg, page_size=8)
        result = recipient.import_prefix_pages(
            chains, contents, dtype=pmap["dtype"],
            page_shape=pmap["page_shape"], page_size=pmap["page_size"])
        assert result == {"pages": 0, "chains": []}
        recipient.allocator.check_conservation()
        assert recipient.allocator.free_count == \
            recipient.allocator.capacity

    def test_pool_pressure_warms_what_fits(self, tiny_llama):
        """Allocator exhaustion mid-import keeps what was allocated
        (a valid prefix), sheds the rest, and conserves."""
        cfg, params = tiny_llama
        donor = warmed_donor(params, cfg)
        pmap, chains, contents = export_all(donor)
        # 2 pool pages, one reserved: exactly ONE allocatable page
        recipient = make_engine(params, cfg, num_pages=2)
        result = recipient.import_prefix_pages(
            chains, contents, dtype=pmap["dtype"],
            page_shape=pmap["page_shape"], page_size=pmap["page_size"])
        assert result["pages"] == 1  # one page fit; the tail shed
        recipient.allocator.check_conservation()
