#!/usr/bin/env python
"""A jax-free replica double: scripted engine worker + the REAL wire.

The process-fleet tests (test_remote.py, test_supervisor.py) need child
processes that boot in milliseconds, stream deterministic tokens, obey
cancel/drain/stall, and die on command — without paying a jax import or
a compile per child. ``FakeEngineWorker`` is an ``EngineWorker``-shaped
double (same duck surface ``ReplicaServer`` documents); run as a script
this module is a drop-in stand-in for ``scripts/replica.py``: it binds
a real ``ReplicaServer``, prints ``READY port=<n>``, drains to exit 0
on SIGTERM, and honors the test-only crash hooks:

  --selfcrash_after_s S --selfcrash_code C   os._exit(C) S seconds
                                             after boot (deterministic
                                             crash-family exits without
                                             racing a kill -9)
  --token_delay_s D                          per-token decode latency
                                             (stretch streams so a test
                                             can kill mid-flight)

Token stream is a pure function of the prompt: token i is
``(sum(prompt) + i) % vocab`` — any observer can recompute the expected
stream, so conservation tests can also assert payload integrity.
"""

from __future__ import annotations

import argparse
import os
import sys
import threading
import time
from types import SimpleNamespace

sys.path.insert(
    0,
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))


class FakeEngineWorker:
    """EngineWorker-shaped double: one thread per request, no jax.

    Matches the surface ``ReplicaServer`` (and the gateway dispatcher)
    relies on: ``submit``/``cancel``/``gauges``/``stall``/``alive``/
    ``inflight``/``page_size``/``shutdown``/``join``/``tick_listeners``.
    """

    def __init__(self, *, token_delay_s: float = 0.005,
                 vocab: int = 101, page_size: int = 16,
                 page_pool: int = 32) -> None:
        self.alive = True
        self.exit_code = None
        self.page_size = page_size
        self.page_pool = page_pool
        self.vocab = vocab
        self.token_delay_s = token_delay_s
        self.tick_listeners = []
        self.draining = False
        self._stall_until = 0.0
        self._lock = threading.Lock()
        self._next_id = 0
        self._live = set()
        self._cancelled = {}
        # warm-rejoin double state: (tokens, pages) chains plus
        # per-page byte contents, same duck surface the real
        # EngineWorker bridges to the engine
        self.warm_pages_total = 0
        self._chains = []
        self._page_contents = {}
        self._next_page = 0

    # -- observability ------------------------------------------------------
    @property
    def inflight(self) -> int:
        with self._lock:
            return len(self._live)

    def gauges(self):
        with self._lock:
            live = len(self._live)
            prefix_pages = len(self._page_contents)
            warm = self.warm_pages_total
        return {
            "queue_depth": 0.0,
            "slot_occupancy": live / 4.0,
            "pages_in_use": float(live),
            "page_pool_free": float(self.page_pool - live),
            "prefix_pages": float(prefix_pages),
            "warm_pages_total": float(warm),
            "decode_compile_count": 1.0,
        }

    # -- control ------------------------------------------------------------
    def stall(self, seconds: float) -> None:
        self._stall_until = time.monotonic() + seconds

    def cancel(self, request_id: int, detail: str) -> None:
        with self._lock:
            if request_id in self._live:
                self._cancelled[request_id] = detail

    def shutdown(self, *, drain: bool = True) -> None:
        self.draining = True

    def join(self, timeout=None) -> None:
        deadline = (time.monotonic() + timeout) if timeout else None
        while self.inflight > 0 and (
                deadline is None or time.monotonic() < deadline):
            time.sleep(0.005)

    def expected_tokens(self, prompt, n):
        base = sum(prompt) % self.vocab
        return [(base + i) % self.vocab for i in range(n)]

    # -- warm-rejoin surface (prefix_map / export / import) -----------------
    @staticmethod
    def page_bytes(page: int, nbytes: int):
        """Deterministic (k, v) contents for a page id — any observer
        can recompute them, so transfer tests assert bit-parity."""
        k = bytes((page * 31 + i) % 256 for i in range(nbytes))
        v = bytes((page * 37 + i + 1) % 256 for i in range(nbytes))
        return k, v

    def seed_prefix(self, tokens) -> int:
        """Register a frozen prefix chain (complete pages only) with
        deterministic contents; returns the number of pages seeded."""
        n = len(tokens) // self.page_size
        if n == 0:
            return 0
        with self._lock:
            pages = list(range(self._next_page, self._next_page + n))
            self._next_page += n
            for p in pages:
                self._page_contents[p] = self.page_bytes(p, self.page_size)
            self._chains.append((list(tokens[:n * self.page_size]), pages))
        return n

    def prefix_map(self):
        with self._lock:
            chains = [{"tokens": list(t), "pages": list(p)}
                      for t, p in self._chains]
            pages = {p: {"refcount": 1, "frozen": True}
                     for p in self._page_contents}
            used = len(self._page_contents)
        return {
            "page_size": self.page_size,
            "dtype": "uint8",
            "page_shape": [1, 1, self.page_size, 1],
            "chains": chains,
            "pages": pages,
            "capacity": self.page_pool,
            "free": self.page_pool - used,
        }

    def export_prefix_pages(self, pages):
        meta = {"dtype": "uint8",
                "page_shape": [1, 1, self.page_size, 1],
                "page_size": self.page_size}
        with self._lock:
            contents = {int(p): self._page_contents[int(p)]
                        for p in pages if int(p) in self._page_contents}
        return meta, contents

    def import_prefix_pages(self, chains, contents, *, dtype,
                            page_shape, page_size) -> dict:
        if page_size != self.page_size or dtype != "uint8":
            return {"pages": 0, "chains": []}
        created, kept = 0, []
        with self._lock:
            mapped = {}
            for tokens, pages in chains:
                valid = 0
                for p in pages:
                    if int(p) in mapped or int(p) in contents:
                        valid += 1
                    else:
                        break
                if valid == 0:
                    continue
                local = []
                for p in pages[:valid]:
                    p = int(p)
                    if p not in mapped:
                        mapped[p] = self._next_page
                        self._next_page += 1
                        self._page_contents[mapped[p]] = contents[p]
                        created += 1
                    local.append(mapped[p])
                tokens = list(tokens[:valid * self.page_size])
                self._chains.append((tokens, local))
                kept.append(tokens)
            self.warm_pages_total += created
        return {"pages": created, "chains": kept}

    def _has_warm_prefix(self, prompt) -> bool:
        with self._lock:
            return any(len(t) <= len(prompt)
                       and list(prompt[:len(t)]) == t
                       for t, _ in self._chains if t)

    # -- the request path ---------------------------------------------------
    def submit(self, req, on_tokens, on_done, *, ttl_s=None,
               on_submitted=None) -> None:
        with self._lock:
            rid = self._next_id
            self._next_id += 1
            self._live.add(rid)
        threading.Thread(
            target=self._run,
            args=(rid, req, on_tokens, on_done, on_submitted),
            name=f"fake-req-{rid}", daemon=True).start()

    def _run(self, rid, req, on_tokens, on_done, on_submitted) -> None:
        if on_submitted is not None:
            on_submitted(rid)
        tokens = []
        outcome, reason, detail = "ok", "length", None
        for tok in self.expected_tokens(req.prompt, req.max_new_tokens):
            while time.monotonic() < self._stall_until:
                time.sleep(0.01)
            time.sleep(self.token_delay_s)
            with self._lock:
                cancel_detail = self._cancelled.pop(rid, None)
            if cancel_detail is not None:
                outcome, reason, detail = "aborted", "aborted", cancel_detail
                break
            tokens.append(tok)
            on_tokens(rid, [tok])
            if req.eos_id is not None and tok == req.eos_id:
                reason = "eos"
                break
        with self._lock:
            self._live.discard(rid)
            self._cancelled.pop(rid, None)
        on_done(SimpleNamespace(
            request_id=rid, prompt=list(req.prompt), tokens=tokens,
            finish_reason=reason, outcome=outcome, detail=detail,
            ttft_s=None, latency_s=None, queue_wait_s=0.0,
            prefill_s=0.0, prefix_hit=self._has_warm_prefix(req.prompt),
            stall_s=0.25, device_wait_s=0.5, host_s=0.125,
            trace_id=req.trace_id))


def parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--replica_id", default="r0")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=0)
    p.add_argument("--token_delay_s", type=float, default=0.005)
    p.add_argument("--drain_timeout_s", type=float, default=10.0)
    p.add_argument("--selfcrash_after_s", type=float, default=0.0)
    p.add_argument("--selfcrash_code", type=int, default=42)
    p.add_argument("--uds", default="",
                   help="Bind a unix-domain socket instead of TCP; "
                        "READY then prints 'READY uds=<path>'.")
    p.add_argument("--warm_chain", default="",
                   help="Comma-separated tokens to seed as a frozen "
                        "prefix chain (complete pages only) so this "
                        "fake can DONATE warm state.")
    p.add_argument("--page_size", type=int, default=4)
    p.add_argument("--ft_gw_warm_donor_crash_at", type=int, default=0)
    p.add_argument("--ft_gw_warm_corrupt_chunk_at", type=int, default=0)
    return p.parse_args(argv)


async def _serve(args, worker) -> None:
    import asyncio
    import signal

    from scaletorch_tpu.inference.resilience import ServingFaultInjector
    from scaletorch_tpu.serving.remote import ReplicaServer

    injector = ServingFaultInjector.from_config(args)
    server = ReplicaServer(
        worker, host=args.host, port=args.port,
        uds=args.uds or None,
        injector=injector if injector.active else None)
    await server.start()
    if args.uds:
        print(f"READY uds={args.uds}", flush=True)
    else:
        print(f"READY port={server.port}", flush=True)
    if args.selfcrash_after_s > 0:
        # armed AFTER READY so the crash clock never races the boot
        timer = threading.Timer(
            args.selfcrash_after_s,
            lambda: os._exit(args.selfcrash_code))
        timer.daemon = True
        timer.start()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGTERM, signal.SIGINT):
        loop.add_signal_handler(sig, server.request_drain)
    await server.wait_drain()
    worker.shutdown(drain=True)
    deadline = time.monotonic() + args.drain_timeout_s
    while worker.inflight > 0 and time.monotonic() < deadline:
        await asyncio.sleep(0.01)
    await server.close()


def main(argv=None) -> int:
    import asyncio

    args = parse_args(argv)
    worker = FakeEngineWorker(token_delay_s=args.token_delay_s,
                              page_size=args.page_size)
    if args.warm_chain:
        worker.seed_prefix(
            [int(t) for t in args.warm_chain.split(",") if t.strip()])
    asyncio.run(_serve(args, worker))
    return 0


if __name__ == "__main__":
    sys.exit(main())
