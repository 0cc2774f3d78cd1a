"""A family's ``forward_cached`` as a compiled program, for the tests
that call it in a loop.

Run operation by operation, a cached forward traces its layer loop anew
at every call: a decode loop of 40 steps pays 40 traces for one shape.
A test that calls a forward in a loop calls a compiled one, and a test
that needs a compiled program finds it in the run's compile cache: a
temporary directory that ``tests/conftest.py`` makes (or the caller's
``JAX_COMPILATION_CACHE_DIR``) and every worker and subprocess of the
run shares, so the second engine over a program, in whatever file or
process, reads what the first compiled and the layouts it asked for
(``decode.load_orders``). The process that made the directory removes
it at the end of its session.
"""

import functools

import jax

from scaletorch_tpu.inference.kv_cache import PagedKVIO


@functools.lru_cache(maxsize=None)
def compiled_forward_cached(forward_cached, cfg):
    """``forward_cached`` with its own signature (and so in the
    harnesses' ``forward_fn`` form, ``decode.teacher_forced_decode`` /
    ``_paged``): one compile per shape and per cache adapter, kept for
    every later caller with the same family and config. Positions,
    masks and logit rows are operands, and so are the adapter's page
    tables; its other fields are static and it is rebuilt inside the
    traced function. The matmul precision in force at the call is part
    of what jit keys a program on."""
    @functools.partial(jax.jit,
                       static_argnames=("adapter", "return_routing"))
    def run(params, tokens, cache, tables, adapter, **operands):
        kv_io = None
        if adapter is not None:
            page_size, seq_limit, kernel, interpret = adapter
            kv_io = PagedKVIO(tables, page_size, seq_limit=seq_limit,
                              kernel=kernel, interpret=interpret)
        return forward_cached(params, tokens, cfg, cache, kv_io=kv_io,
                              **operands)

    def fwd(params, tokens, _cfg, cache, *, kv_io=None, **operands):
        if kv_io is None:
            return run(params, tokens, cache, None, None, **operands)
        return run(params, tokens, cache, kv_io.page_tables,
                   (kv_io.page_size, kv_io.seq_limit, kv_io.kernel,
                    kv_io.interpret), **operands)

    return fwd
