"""Elastic training fleet (resilience_distributed.ElasticCoordinator):
survive host loss by remeshing, not restarting.

The acceptance surface of the elastic layer, exercised hermetically in
one process. The end-to-end drills run the REAL ``Trainer.train``
remesh-and-resume outer loop / ``CoordinatedResilience`` /
``CheckpointManager`` on N simulated host threads over the REAL
``FileBus`` (deadline-bounded file collectives — the same transport
production uses for post-remesh epochs) and the shared
``FileMembershipStore``.

Covered here:
  * kill drill (``--ft_kill_host_at_step`` / ``--ft_kill_host``): host 2
    hard-killed after step 3 -> survivors detect the loss via the
    bounded collective deadline, agree a shrink epoch, restore from the
    latest checkpoint, continue to the absolute ``total_train_steps``
    target; a relaunched replacement parks at the rejoin barrier and is
    readmitted at the next checkpoint boundary — final params BITWISE
    equal to an undisturbed run;
  * hang drill (``--ft_host_hang_elastic``): a live-but-wedged host is
    evicted, wakes to find the fleet moved on, parks, and aborts loudly
    (ElasticRemeshError) when no grow boundary admits it;
  * membership transitions attested in JSONL telemetry (``membership``
    kind) + counters;
  * the epoch state machine unit-by-unit: suspect-round agreement,
    write-once epoch records, min-hosts floor, spurious-loss remesh in
    place, eviction -> park -> rejoin, grow via the epoch bus;
  * FileMembershipStore / FileBus / MembershipView primitives;
  * ``elastic_mesh_kwargs``: dp absorbs the host change, un-shrinkable
    geometries refuse loudly;
  * dp4 -> dp2 -> dp4 checkpoint round-trip pinning bitwise param /
    opt-state equality across ``load_latest(target_mesh=...)``;
  * ``remap_loader_position``: never double-counts, never skips a batch
    on a divisor shrink, composes with rollback skew;
  * the parse-time rejection matrix for ``--elastic``.
"""

import os
import threading
import time
from functools import partial

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from scaletorch_tpu.config import ScaleTorchTPUArguments
from scaletorch_tpu.data.dataloader import (
    MicroBatchDataLoader,
    SyntheticDataLoader,
    remap_loader_position,
)
from scaletorch_tpu.parallel.mesh import (
    MeshManager,
    MeshShrinkError,
    elastic_mesh_kwargs,
)
from scaletorch_tpu.resilience import FaultInjector, HostKilledError
from scaletorch_tpu.resilience_distributed import (
    CoordinatedResilience,
    DecisionBus,
    ElasticCoordinator,
    ElasticRemeshError,
    FileBus,
    FileMembershipStore,
    MembershipView,
    PeerLostError,
    _elastic_wrap,
    elastic_decision_bus,
)
from scaletorch_tpu.telemetry.export import (
    KNOWN_KINDS,
    TelemetryExporter,
    read_jsonl,
)
from tests.test_resilience import ToyTrainer, e2e_cfg, e2e_tokens


# ---------------------------------------------------------------------------
# Helpers
# ---------------------------------------------------------------------------


def wait_until(pred, timeout=30.0, poll=0.01, what="condition"):
    deadline = time.monotonic() + timeout
    while not pred():
        assert time.monotonic() < deadline, f"timed out waiting for {what}"
        time.sleep(poll)


def run_threads(fns, timeout=120.0):
    """Run ``{name: fn}`` on daemon threads; returns (results, errors)
    dicts. Catches BaseException: ``HostKilledError`` deliberately is
    NOT an Exception and must still be recorded, not dumped to stderr."""
    results, errors = {}, {}

    def worker(name, fn):
        try:
            results[name] = fn()
        except BaseException as exc:  # noqa: BLE001 — surfaced via errors
            errors[name] = exc

    threads = [threading.Thread(target=worker, args=(n, f), daemon=True)
               for n, f in fns.items()]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout)
    assert not any(t.is_alive() for t in threads), \
        "a simulated host wedged (elastic protocol desync?)"
    return results, errors


def file_bus_factory(store, deadline):
    """The production transport (FileBus over the membership directory),
    with a test-sized deadline."""

    def factory(view, rank):
        fb = FileBus(
            os.path.join(store.directory, "collective"),
            epoch=view.epoch, members=view.members, rank=rank,
            deadline=deadline,
        )
        return DecisionBus(
            num_processes=view.num_hosts,
            process_index=view.bus_index(rank),
            all_gather=fb.all_gather,
            broadcast=fb.broadcast,
        )

    return factory


def assert_trees_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def membership_events(path):
    return [e for e in read_jsonl(path) if e.get("kind") == "membership"]


def transitions(path):
    """Non-steady transitions (the founding 'steady' event is emitted
    only by ranks that raced to write the founding record first)."""
    return [e["transition"] for e in membership_events(path)
            if e["transition"] != "steady"]


def _raise_killed():
    raise HostKilledError("injected host kill")


def _reference_params(tmp_path, **kw):
    """An undisturbed single-trainer run — the bitwise oracle the
    elastic fleet must reproduce."""
    cfg = e2e_cfg(tmp_path / "ref", **kw)
    t = ToyTrainer(cfg, e2e_tokens())
    t.train()
    t.close()
    return t.params


# ---------------------------------------------------------------------------
# End-to-end drills: the REAL Trainer.train remesh-and-resume loop
# ---------------------------------------------------------------------------


@pytest.mark.multihost
class TestElasticDrills:
    FLEET = 4
    DEADLINE = 2.0

    def _fleet_kw(self, **extra):
        kw = dict(
            total_train_steps=5, resume="auto", elastic=True,
            elastic_deadline_seconds=self.DEADLINE,
            elastic_heartbeat_seconds=0.2,
        )
        kw.update(extra)
        return kw

    def _make_host(self, i, tmp_path, ckpt_dir, exporter_name, **cfg_kw):
        cfg = e2e_cfg(ckpt_dir, **self._fleet_kw(**cfg_kw))
        t = ToyTrainer(cfg, e2e_tokens())
        inj = t.resilience.injector
        inj.host_index = i
        inj.deliver_kill = _raise_killed
        exporter = TelemetryExporter(
            str(tmp_path / "telem" / f"{exporter_name}.jsonl"),
            process_index=i)
        t.elastic = ElasticCoordinator.from_config(
            cfg, rank=i, num_hosts=self.FLEET, exporter=exporter,
            store=FileMembershipStore(str(tmp_path / "membership")))
        t._test_exporter = exporter
        return t

    def test_kill_drill_shrink_restore_regrow_bitwise(self, tmp_path):
        """Host 2 killed after step 3: survivors shrink (epoch 1),
        restore the step-2 checkpoint, continue; a relaunched rank 2
        parks and is readmitted at the step-4 checkpoint boundary
        (epoch 2); every finisher's params are bitwise equal to an
        undisturbed run's, and the full epoch sequence is attested in
        membership JSONL + counters."""

        def fleet_host(i):
            t = self._make_host(
                i, tmp_path, tmp_path / f"host{i}",
                exporter_name=f"host{i}",
                ft_kill_host_at_step=3, ft_kill_host=2)
            t.coordinator = CoordinatedResilience(
                t.resilience, bus=t.elastic.bus)
            t.train()
            t.close()
            t._test_exporter.close()
            return t

        def relaunched_host():
            # a real launcher (scripts/launch_multihost.sh ELASTIC=1)
            # relaunches ONLY the dead rank after its crash-family exit;
            # polling the store for the shrink epoch stands in for that
            # process-scheduling delay
            store = FileMembershipStore(str(tmp_path / "membership"))
            wait_until(
                lambda: (store.latest_epoch() or {}).get("epoch", -1) >= 1,
                timeout=60.0, what="the shrink epoch record")
            # the coordinator must exist (parked) BEFORE the rejoin
            # request: a grow that fires mid-construction is then
            # handled by join()'s poll instead of racing the view
            cfg = e2e_cfg(tmp_path / "host0",
                          **self._fleet_kw(save_frequency=0))
            exporter = TelemetryExporter(
                str(tmp_path / "telem" / "host2b.jsonl"), process_index=2)
            coord = ElasticCoordinator.from_config(
                cfg, rank=2, num_hosts=self.FLEET, exporter=exporter,
                store=store)
            assert coord.parked and coord.needs_join
            store.request_rejoin(2)
            t = ToyTrainer(cfg, e2e_tokens())
            t.resilience.injector.host_index = 2
            t.elastic = coord
            t.coordinator = CoordinatedResilience(t.resilience)
            t.train()
            t.close()
            exporter.close()
            return t

        fns = {i: partial(fleet_host, i) for i in range(self.FLEET)}
        fns["2b"] = relaunched_host
        results, errors = run_threads(fns)

        # the killed host unwound on the BaseException kill — nothing
        # between the injection site and the thread top caught it
        assert isinstance(errors.pop(2), HostKilledError)
        assert errors == {}

        expected = _reference_params(tmp_path, total_train_steps=5)
        final_view = MembershipView(2, (0, 1, 2, 3))
        for name in (0, 1, 3, "2b"):
            t = results[name]
            assert t.global_step == 5
            assert t.elastic.view == final_view
            assert t.loader.position == 5 and t._loader_skew == 0
            assert_trees_equal(t.params, expected)

        # counters: one loss event -> one suspect round -> one shrink,
        # then one grow readmitting the relaunched rank
        c0 = results[0].elastic.counters()
        assert c0["elastic_peer_loss_events"] == 1
        assert c0["elastic_suspect_rounds"] == 1
        assert c0["elastic_shrinks"] == 1 and c0["elastic_grows"] == 1
        assert c0["elastic_hosts_lost"] == 1
        assert c0["elastic_hosts_rejoined"] == 1
        assert c0["elastic_epochs_adopted"] == 2
        assert c0["elastic_evictions"] == 0
        cb = results["2b"].elastic.counters()
        assert cb["elastic_epochs_adopted"] == 1
        assert cb["elastic_hosts_rejoined"] == 1
        assert cb["elastic_evictions"] == 0

        # membership JSONL: the full epoch sequence, per rank
        for i in (0, 1, 3):
            events = membership_events(
                tmp_path / "telem" / f"host{i}.jsonl")
            assert transitions(
                tmp_path / "telem" / f"host{i}.jsonl"
            ) == ["suspect", "shrink", "grow"]
            by = {e["transition"]: e for e in events}
            assert by["shrink"]["epoch"] == 1
            assert by["shrink"]["members"] == [0, 1, 3]
            assert by["shrink"]["lost"] == [2]
            assert by["grow"]["epoch"] == 2
            assert by["grow"]["members"] == [0, 1, 2, 3]
            assert by["grow"]["joined"] == [2]
            for e in events:
                assert e["kind"] == "membership" and e["rank"] == i
                assert e["num_hosts"] == len(e["members"])
        assert transitions(tmp_path / "telem" / "host2b.jsonl") == ["join"]
        (join_ev,) = [e for e in membership_events(
            tmp_path / "telem" / "host2b.jsonl")
            if e["transition"] == "join"]
        assert join_ev["epoch"] == 2 and join_ev["joined"] == [2]

        # store surfaces: epoch chain on disk, mailbox drained,
        # operator-visible heartbeats refreshed
        store = FileMembershipStore(str(tmp_path / "membership"))
        assert [store.epoch(n)["reason"] for n in (0, 1, 2)] \
            == ["found", "shrink", "grow"]
        assert store.pending_rejoins() == []
        assert os.path.exists(
            os.path.join(store.directory, "heartbeat_r0.json"))

    def test_hang_drill_evicts_wedged_host(self, tmp_path):
        """Host 2 stalls past the elastic deadline: the fleet evicts it
        and continues to the target bitwise-identically; the wedged host
        wakes, finds the epoch moved on, parks, and aborts loudly when
        no grow boundary ever admits it."""

        def host(i):
            # the hang must outlast loss detection (one deadline) PLUS
            # the survivors' alive round (another deadline), or the
            # wedged host answers the roll call and stays a member
            t = self._make_host(
                i, tmp_path, tmp_path / f"host{i}",
                exporter_name=f"host{i}",
                ft_host_hang_elastic=3, ft_kill_host=2,
                ft_host_hang_seconds=2 * self.DEADLINE + 1.5)
            if i == 2:
                # nobody relaunches anything in this drill: the parked
                # host must give up in bounded time, not block the test
                t.elastic.join_timeout = 3.0
            # "no grow boundary ever admits it" is the drill's premise,
            # not an accident of who is faster: under load the survivors
            # may still be at a checkpoint boundary when host 2 wakes
            # and parks, and an open boundary would lawfully readmit it
            # (epoch 2, no abort). Every host skips the boundary alike
            # (host 2 too, a member until it hangs), so the bus carries
            # the same collectives on each.
            t._maybe_elastic_grow = lambda: None
            t.coordinator = CoordinatedResilience(
                t.resilience, bus=t.elastic.bus)
            t.train()
            t.close()
            t._test_exporter.close()
            return t

        results, errors = run_threads(
            {i: partial(host, i) for i in range(self.FLEET)})

        err = errors.pop(2)
        assert isinstance(err, ElasticRemeshError)
        assert "rejoin barrier" in str(err)
        assert errors == {}

        expected = _reference_params(tmp_path, total_train_steps=5)
        for i in (0, 1, 3):
            t = results[i]
            assert t.global_step == 5
            assert t.elastic.view == MembershipView(1, (0, 1, 3))
            assert_trees_equal(t.params, expected)
            assert transitions(
                tmp_path / "telem" / f"host{i}.jsonl"
            ) == ["suspect", "shrink"]
            assert t.elastic.counters()["elastic_hosts_lost"] == 1


# ---------------------------------------------------------------------------
# ElasticCoordinator state machine (store-level, no trainer)
# ---------------------------------------------------------------------------


class TestElasticCoordinator:
    def _coord(self, store, rank, *, num_hosts=3, deadline=0.4, **kw):
        return ElasticCoordinator(
            rank=rank, num_hosts=num_hosts, store=store,
            bus_factory=file_bus_factory(store, deadline),
            deadline_seconds=deadline, **kw)

    def test_founding_epoch_and_view(self, tmp_path):
        store = FileMembershipStore(str(tmp_path))
        c = self._coord(store, 0)
        assert c.view == MembershipView(0, (0, 1, 2))
        assert c.state == "steady" and not c.needs_join
        assert store.epoch(0)["reason"] == "found"
        # a later construction adopts the record instead of re-founding
        c2 = self._coord(store, 1)
        assert c2.view == c.view and c2.state == "steady"

    def test_relaunched_excluded_rank_parks(self, tmp_path):
        store = FileMembershipStore(str(tmp_path))
        store.propose_epoch({"epoch": 0, "members": [0, 1, 2],
                             "reason": "found", "step": None})
        store.propose_epoch({"epoch": 1, "members": [0, 1],
                             "reason": "shrink", "step": 3})
        c = self._coord(store, 2)
        assert c.parked and c.needs_join
        assert c.view == MembershipView(1, (0, 1))

    def test_suspect_round_agrees_shrink_epoch(self, tmp_path):
        store = FileMembershipStore(str(tmp_path))
        coords = {r: self._coord(store, r) for r in (0, 1)}  # rank 2 dead
        results, errors = run_threads(
            {r: partial(c.on_peer_lost, 5) for r, c in coords.items()},
            timeout=30.0)
        assert errors == {}
        assert results[0] == results[1] == MembershipView(1, (0, 1))
        for c in coords.values():
            cc = c.counters()
            assert cc["elastic_suspect_rounds"] == 1
            assert cc["elastic_shrinks"] == 1
            assert cc["elastic_hosts_lost"] == 1
        assert store.epoch(1)["step"] == 5

    def test_spurious_loss_remeshes_in_place(self, tmp_path):
        # every member answers the suspect round: same member set, new
        # epoch — the fleet re-synchronises without shedding anyone
        store = FileMembershipStore(str(tmp_path))
        coords = {r: self._coord(store, r) for r in range(3)}
        results, errors = run_threads(
            {r: partial(c.on_peer_lost, 7) for r, c in coords.items()},
            timeout=30.0)
        assert errors == {}
        assert all(v == MembershipView(1, (0, 1, 2))
                   for v in results.values())
        assert coords[0].counters()["elastic_hosts_lost"] == 0

    def test_min_hosts_floor_aborts_to_fleet_restart(self, tmp_path):
        store = FileMembershipStore(str(tmp_path))
        coords = {r: self._coord(store, r, min_hosts=3) for r in (0, 1)}
        _, errors = run_threads(
            {r: partial(c.on_peer_lost, 5) for r, c in coords.items()},
            timeout=30.0)
        assert all(isinstance(e, ElasticRemeshError)
                   for e in errors.values()) and len(errors) == 2
        assert all("elastic_min_hosts" in str(e) for e in errors.values())

    def test_evicted_host_parks_then_rejoins(self, tmp_path):
        store = FileMembershipStore(str(tmp_path))
        store.propose_epoch({"epoch": 0, "members": [0, 1, 2],
                             "reason": "found", "step": None})
        c2 = self._coord(store, 2)
        assert c2.state == "steady"
        # the fleet moved on without rank 2 (it hung past the deadline)
        store.propose_epoch({"epoch": 1, "members": [0, 1],
                             "reason": "shrink", "step": 9})
        out = {}
        th = threading.Thread(
            target=lambda: out.update(view=c2.on_peer_lost(9)),
            daemon=True)
        th.start()
        wait_until(lambda: store.pending_rejoins() == [2],
                   what="the rejoin request")
        store.propose_epoch({"epoch": 2, "members": [0, 1, 2],
                             "reason": "grow", "step": 10})
        th.join(10.0)
        assert not th.is_alive()
        assert out["view"] == MembershipView(2, (0, 1, 2))
        assert c2.pending_bootstrap and c2.needs_join
        assert c2.counters()["elastic_evictions"] == 1

    def test_join_timeout_is_loud(self, tmp_path):
        store = FileMembershipStore(str(tmp_path))
        store.propose_epoch({"epoch": 0, "members": [0],
                             "reason": "found", "step": None})
        c = self._coord(store, 1, num_hosts=2, join_timeout=0.3)
        assert c.parked
        with pytest.raises(ElasticRemeshError, match="rejoin barrier"):
            c.join(step=1)

    def test_maybe_grow_admits_parked_rank(self, tmp_path):
        store = FileMembershipStore(str(tmp_path))
        store.propose_epoch({"epoch": 0, "members": [0, 1],
                             "reason": "found", "step": None})
        store.propose_epoch({"epoch": 1, "members": [0],
                             "reason": "shrink", "step": 3})
        c0 = self._coord(store, 0, num_hosts=2, deadline=5.0)
        c1 = self._coord(store, 1, num_hosts=2, deadline=5.0)
        assert c0.view.members == (0,) and c1.parked
        assert c0.maybe_grow(step=4) is None  # empty mailbox: no-op
        out = {}
        th = threading.Thread(
            target=lambda: out.update(view=c1.join(step=4)), daemon=True)
        th.start()
        wait_until(lambda: store.pending_rejoins() == [1],
                   what="the rejoin request")
        view = c0.maybe_grow(step=4)
        th.join(10.0)
        assert not th.is_alive()
        assert view == out["view"] == MembershipView(2, (0, 1))
        assert store.pending_rejoins() == []  # mailbox drained
        assert c0.counters()["elastic_grows"] == 1
        assert c1.counters()["elastic_hosts_rejoined"] == 1
        assert c1.pending_bootstrap

    def test_beat_writes_heartbeat(self, tmp_path):
        store = FileMembershipStore(str(tmp_path))
        c = self._coord(store, 0, heartbeat_seconds=0.01)
        c.beat(step=7)
        import json

        with open(os.path.join(store.directory, "heartbeat_r0.json")) as f:
            hb = json.load(f)
        assert hb["rank"] == 0 and hb["step"] == 7 and hb["epoch"] == 0


# ---------------------------------------------------------------------------
# Primitives: store, bus, view, wrap
# ---------------------------------------------------------------------------


class TestMembershipPrimitives:
    def test_epoch_records_are_write_once(self, tmp_path):
        store = FileMembershipStore(str(tmp_path))
        assert store.propose_epoch(
            {"epoch": 1, "members": [0, 1], "reason": "shrink", "step": 3})
        assert not store.propose_epoch(
            {"epoch": 1, "members": [9], "reason": "shrink", "step": 3})
        assert store.epoch(1)["members"] == [0, 1]  # first writer won
        store.propose_epoch(
            {"epoch": 2, "members": [0], "reason": "shrink", "step": 4})
        assert store.latest_epoch()["epoch"] == 2

    def test_alive_and_rejoin_surfaces(self, tmp_path):
        store = FileMembershipStore(str(tmp_path))
        store.post_alive(3, 0, step=5)
        store.post_alive(3, 2, step=5)
        store.post_alive(4, 1, step=9)  # different epoch: not counted
        assert store.alive_set(3) == {0, 2}
        store.request_rejoin(7)
        store.request_rejoin(4)
        assert store.pending_rejoins() == [4, 7]
        store.clear_rejoin(4)
        store.clear_rejoin(4)  # idempotent
        assert store.pending_rejoins() == [7]

    def test_file_bus_gathers_in_member_order(self, tmp_path):
        fbs = {r: FileBus(str(tmp_path), epoch=0, members=(1, 3), rank=r,
                          deadline=5.0) for r in (1, 3)}
        results, errors = run_threads({
            r: partial(fb.all_gather, f"v{r}") for r, fb in fbs.items()})
        assert errors == {}
        assert results[1] == results[3] == ["v1", "v3"]
        # broadcast src indexes the MEMBERS tuple, not global ranks
        results, errors = run_threads({
            r: partial(fb.broadcast, [f"payload{r}"])
            for r, fb in fbs.items()})
        assert errors == {}
        assert results[1] == results[3] == ["payload1"]

    def test_file_bus_names_the_missing_rank(self, tmp_path):
        fb = FileBus(str(tmp_path), epoch=2, members=(0, 5), rank=0,
                     deadline=0.2)
        with pytest.raises(PeerLostError) as ei:
            fb.all_gather("x")
        assert ei.value.missing == (5,)
        assert "5" in str(ei.value)

    def test_membership_view_renumbers_ranks(self):
        view = MembershipView(3, (0, 2, 5))
        assert view.num_hosts == 3
        assert [view.bus_index(r) for r in (0, 2, 5)] == [0, 1, 2]
        bus = elastic_decision_bus(
            view, 5, DecisionBus(
                num_processes=3, process_index=2,
                all_gather=lambda obj: [obj] * 3,
                broadcast=lambda objs: objs))
        assert bus.process_index == 2 and not bus.is_main
        assert elastic_decision_bus(
            view, 0, DecisionBus(
                num_processes=3, process_index=0,
                all_gather=lambda obj: [obj] * 3,
                broadcast=lambda objs: objs)).is_main

    def test_elastic_wrap_normalises_transport_loss(self):
        def broken(*_):
            raise threading.BrokenBarrierError()

        with pytest.raises(PeerLostError):
            _elastic_wrap(broken)("x")

        def already(*_):
            raise PeerLostError("gone", missing=(3,))

        with pytest.raises(PeerLostError) as ei:
            _elastic_wrap(already)("x")
        assert ei.value.missing == (3,)  # not double-wrapped

    def test_membership_is_a_known_telemetry_kind(self):
        assert "membership" in KNOWN_KINDS


# ---------------------------------------------------------------------------
# Mesh geometry: dp absorbs the host change
# ---------------------------------------------------------------------------


class TestElasticMeshKwargs:
    BASE = dict(dp=8, pp=1, cp=1, ep=1, tp=2)

    def test_shrink_halves_dp_only(self):
        out = elastic_mesh_kwargs(self.BASE, hosts_before=4, hosts_after=2)
        assert out == dict(dp=4, pp=1, cp=1, ep=1, tp=2)

    def test_grow_restores_dp(self):
        shrunk = elastic_mesh_kwargs(
            self.BASE, hosts_before=4, hosts_after=2)
        regrown = elastic_mesh_kwargs(
            shrunk, hosts_before=2, hosts_after=4)
        assert regrown == self.BASE

    def test_unshrinkable_dp_refuses_loudly(self):
        with pytest.raises(MeshShrinkError, match="fleet restart"):
            elastic_mesh_kwargs(
                dict(self.BASE, dp=6), hosts_before=4, hosts_after=3)

    def test_bad_host_counts_refused(self):
        with pytest.raises(MeshShrinkError):
            elastic_mesh_kwargs(self.BASE, hosts_before=4, hosts_after=0)


# ---------------------------------------------------------------------------
# Checkpoint topology round-trip: dp4 -> dp2 -> dp4, bitwise
# ---------------------------------------------------------------------------


class TestCheckpointReshard:
    def _cm(self, tmp_path):
        from scaletorch_tpu.utils.checkpoint import CheckpointManager

        return CheckpointManager(str(tmp_path), async_save=False,
                                 retries=0, retry_base_delay=0.01)

    def test_dp4_dp2_dp4_round_trip_is_bitwise(self, tmp_path, devices8):
        mm4 = MeshManager(dp=4, tp=2)
        # the post-shrink world: half the hosts -> half the devices
        mm2 = MeshManager(dp=2, tp=2, devices=devices8[:4])
        rng = np.random.default_rng(0)
        host_params = {
            "w": rng.standard_normal((8, 8)).astype(np.float32),
            "b": rng.standard_normal((8,)).astype(np.float32),
        }
        host_opt = {"m": rng.standard_normal((8, 8)).astype(np.float32)}

        def place(mesh, tree, specs):
            return {k: jax.device_put(v, NamedSharding(mesh, specs[k]))
                    for k, v in tree.items()}

        p_specs = {"w": P("dp", "tp"), "b": P()}
        o_specs = {"m": P("dp", "tp")}
        params4 = place(mm4.mesh, host_params, p_specs)
        opt4 = place(mm4.mesh, host_opt, o_specs)
        cm = self._cm(tmp_path)
        assert cm.save(1, params=params4, opt_state=opt4,
                       extra={"samples_per_step": 16})
        cm.wait()

        out2 = cm.load_latest(params=params4, opt_state=opt4,
                              target_mesh=mm2.mesh)
        assert out2 is not None and out2["step"] == 1
        for k in host_params:
            leaf = out2["params"][k]
            # resharded onto the SMALLER mesh, same spec, bitwise values
            assert dict(leaf.sharding.mesh.shape)["dp"] == 2
            assert leaf.sharding.spec == p_specs[k]
            np.testing.assert_array_equal(np.asarray(leaf), host_params[k])
        np.testing.assert_array_equal(
            np.asarray(out2["opt_state"]["m"]), host_opt["m"])

        # scale back up: the dp2-resident arrays are the restore
        # templates this time (exactly the grow path)
        out4 = cm.load_latest(params=out2["params"],
                              opt_state=out2["opt_state"],
                              target_mesh=mm4.mesh)
        assert out4 is not None
        for k in host_params:
            leaf = out4["params"][k]
            assert dict(leaf.sharding.mesh.shape)["dp"] == 4
            np.testing.assert_array_equal(np.asarray(leaf), host_params[k])
        np.testing.assert_array_equal(
            np.asarray(out4["opt_state"]["m"]), host_opt["m"])
        assert out4["extra"]["samples_per_step"] == 16

    def test_retarget_tree_replicates_unsharded_leaves(self, devices8):
        from scaletorch_tpu.utils.checkpoint import retarget_tree

        mm2 = MeshManager(dp=2, tp=2, devices=devices8[:4])
        tree = {"host": np.ones((4,), np.float32), "scalar": 3}
        out = retarget_tree(tree, mm2.mesh)
        assert out["host"].shape == (4,)
        assert out["host"].sharding.spec == P()
        assert out["scalar"].shape == ()


# ---------------------------------------------------------------------------
# Loader position remap: every consumed batch retired exactly once
# ---------------------------------------------------------------------------


def _rows(n=64, seq=8):
    # each sequence row is its own index everywhere: batch contents
    # identify exactly which samples were consumed
    return np.tile(np.arange(n, dtype=np.int32)[:, None], (1, seq + 1))


def _loader(tokens, dp):
    return MicroBatchDataLoader(
        tokens, micro_batch_size=1, gradient_accumulation_steps=1,
        data_parallel_size=dp, seed=7)


def _drawn_samples(batch):
    return sorted(np.unique(batch["input_ids"]).tolist())


class TestLoaderRemap:
    def test_remap_arithmetic(self):
        assert remap_loader_position(
            3, old_samples_per_step=4, new_samples_per_step=2) == 6
        assert remap_loader_position(
            0, old_samples_per_step=4, new_samples_per_step=2) == 0
        assert remap_loader_position(
            5, old_samples_per_step=4, new_samples_per_step=4) == 5
        # non-exact grow rounds UP: partially-covered step batch retired
        assert remap_loader_position(
            3, old_samples_per_step=2, new_samples_per_step=4) == 2
        with pytest.raises(ValueError):
            remap_loader_position(
                1, old_samples_per_step=0, new_samples_per_step=4)
        with pytest.raises(ValueError):
            remap_loader_position(
                -1, old_samples_per_step=2, new_samples_per_step=4)

    def test_remap_never_replays_a_consumed_sample(self):
        for pos in range(0, 9):
            for old in (2, 3, 4, 8):
                for new in (2, 3, 4, 8):
                    got = remap_loader_position(
                        pos, old_samples_per_step=old,
                        new_samples_per_step=new)
                    consumed = pos * old
                    assert got * new >= consumed  # nothing double-counted
                    # and strictly less than one new step batch skipped
                    assert got * new - consumed < new

    def test_divisor_shrink_is_exact_end_to_end(self):
        tokens = _rows()
        big = _loader(tokens, dp=4)       # samples_per_step = 4
        it = iter(big)
        consumed = []
        for _ in range(3):
            consumed += _drawn_samples(next(it))
        new_pos = remap_loader_position(
            big.position, old_samples_per_step=big.samples_per_step,
            new_samples_per_step=2)
        assert new_pos == 6
        small = _loader(tokens, dp=2)      # samples_per_step = 2
        small.set_state(new_pos)
        # reference: an undisturbed dp2 walk of the SAME permutation
        ref = _loader(tokens, dp=2)
        ref_it = iter(ref)
        ref_consumed = []
        for _ in range(6):
            ref_consumed += _drawn_samples(next(ref_it))
        # the dp4 prefix covered exactly the first 6 dp2 steps' samples
        assert sorted(consumed) == sorted(ref_consumed)
        # and the remapped stream continues IDENTICALLY to the reference
        small_it = iter(small)
        for _ in range(4):
            a, b = next(small_it), next(ref_it)
            np.testing.assert_array_equal(a["input_ids"], b["input_ids"])

    def test_non_exact_grow_skips_lt_one_step_and_warns(self):
        import logging

        tokens = _rows()
        small = _loader(tokens, dp=2)      # spp 2
        it = iter(small)
        consumed = []
        for _ in range(3):                 # 6 samples consumed
            consumed += _drawn_samples(next(it))
        # the package logger does not propagate to root (so caplog
        # misses it): attach a capture handler directly
        records = []
        handler = logging.Handler()
        handler.emit = records.append
        pkg_logger = logging.getLogger("scaletorch_tpu")
        pkg_logger.addHandler(handler)
        try:
            new_pos = remap_loader_position(
                small.position, old_samples_per_step=2,
                new_samples_per_step=4)
        finally:
            pkg_logger.removeHandler(handler)
        assert new_pos == 2                # 8 samples retired, 2 skipped
        assert any("rounding up" in r.getMessage() for r in records)
        big = _loader(tokens, dp=4)
        big.set_state(new_pos)
        nxt = _drawn_samples(next(iter(big)))
        # never re-consumes anything already trained on
        assert not set(nxt) & set(consumed)

    def test_set_data_parallel_size_validates(self):
        tokens = _rows(n=8)
        loader = _loader(tokens, dp=2)
        with pytest.raises(ValueError):
            loader.set_data_parallel_size(0)
        with pytest.raises(ValueError, match="after the dp change"):
            loader.set_data_parallel_size(16)
        loader.set_data_parallel_size(4)
        assert loader.samples_per_step == 4
        syn = SyntheticDataLoader(
            vocab_size=16, sequence_length=8, micro_batch_size=2,
            gradient_accumulation_steps=1, data_parallel_size=2)
        syn.set_data_parallel_size(4)
        assert syn.global_batch_size == 8
        with pytest.raises(ValueError):
            syn.set_data_parallel_size(0)

    def test_load_checkpoint_remaps_position_across_dp_change(
            self, tmp_path):
        cfg = e2e_cfg(tmp_path, total_train_steps=4)
        t = ToyTrainer(cfg, e2e_tokens())
        t.train()  # saves step 4 with samples_per_step=4, position=4
        t.close()
        t2 = ToyTrainer(cfg, e2e_tokens())
        t2.loader.set_data_parallel_size(2)  # spp 4 -> 8
        assert t2.load_checkpoint()
        assert t2.global_step == 4
        # 16 samples consumed = exactly 2 steps of the new geometry
        assert t2.loader.position == 2
        assert t2._loader_skew == -2

    def test_remap_composes_with_rollback_skew(self, tmp_path):
        # PR-1 rollback skew: the retired anomalous batch keeps position
        # AHEAD of global_step; a dp change must remap that skewed
        # position, not the step counter
        cfg = e2e_cfg(tmp_path, divergence_policy="rollback",
                      ft_nan_at_step=3)
        t = ToyTrainer(cfg, e2e_tokens())
        t.train()   # ends step 6, position 7 (skew 1), saved at step 6
        t.close()
        assert t.loader.position == 7
        t2 = ToyTrainer(cfg, e2e_tokens())
        t2.resilience.injector.nan_at_step = 0
        t2.loader.set_data_parallel_size(2)  # spp 4 -> 8
        assert t2.load_checkpoint()
        assert t2.global_step == 6
        # 28 samples consumed -> ceil to 4 new steps (32 retired):
        # the skipped anomalous region stays retired
        assert t2.loader.position == 4
        assert t2._loader_skew == -2


# ---------------------------------------------------------------------------
# Fault injector drills + env parity
# ---------------------------------------------------------------------------


class TestElasticInjector:
    def test_kill_targets_one_host_and_fires_once(self):
        fired = []
        inj = FaultInjector(kill_host_at_step=3, kill_host=1,
                            host_index=0, deliver_kill=lambda: fired.append(1))
        inj.maybe_kill(3)
        assert fired == []          # not this host
        inj.host_index = 1
        inj.maybe_kill(2)
        assert fired == []          # not this step
        inj.maybe_kill(3)
        inj.maybe_kill(3)
        assert fired == [1]         # exactly once
        assert inj.active

    def test_default_kill_delivery_raises_nothing_catchable(self):
        # the test delivery is a BaseException by design
        with pytest.raises(HostKilledError):
            _raise_killed()
        assert not issubclass(HostKilledError, Exception)

    def test_elastic_hang_stalls_once(self):
        inj = FaultInjector(host_hang_elastic=2, host_hang_seconds=0.05,
                            host_index=0)
        t0 = time.monotonic()
        inj.maybe_elastic_hang(2)
        assert time.monotonic() - t0 >= 0.05
        t0 = time.monotonic()
        inj.maybe_elastic_hang(2)   # fired already
        assert time.monotonic() - t0 < 0.05
        assert inj.active

    def test_env_overrides_config(self, monkeypatch):
        cfg = e2e_cfg()
        monkeypatch.setenv("SCALETORCH_TPU_FT_KILL_HOST_STEP", "7")
        monkeypatch.setenv("SCALETORCH_TPU_FT_KILL_HOST", "2")
        monkeypatch.setenv("SCALETORCH_TPU_FT_HOST_HANG_ELASTIC", "4")
        inj = FaultInjector.from_config(cfg)
        assert inj.kill_host_at_step == 7
        assert inj.kill_host == 2
        assert inj.host_hang_elastic == 4

    def test_present_env_cancels_config_armed_drill(self, monkeypatch):
        cfg = e2e_cfg(ft_kill_host_at_step=9)
        monkeypatch.setenv("SCALETORCH_TPU_FT_KILL_HOST_STEP", "0")
        assert FaultInjector.from_config(cfg).kill_host_at_step == 0


# ---------------------------------------------------------------------------
# Parse-time rejection matrix
# ---------------------------------------------------------------------------


class TestElasticConfigValidation:
    def _cfg(self, tmp_path=None, **kw):
        base = dict(elastic=True, resume="auto")
        if tmp_path is not None:
            base["checkpoint_dir"] = str(tmp_path)
        base.update(kw)
        return ScaleTorchTPUArguments(**base)

    def test_valid_elastic_config_parses(self, tmp_path):
        cfg = self._cfg(tmp_path, num_processes=4, data_parallel_size=8,
                        elastic_min_hosts=2)
        assert cfg.elastic and cfg.elastic_min_hosts == 2

    def test_requires_checkpoint_dir(self):
        with pytest.raises(ValueError, match="checkpoint_dir"):
            self._cfg()

    def test_requires_resume(self, tmp_path):
        with pytest.raises(ValueError, match="--resume auto"):
            self._cfg(tmp_path, resume="off")

    def test_resume_must_composes(self, tmp_path):
        assert self._cfg(tmp_path, resume="must").resume == "must"

    def test_min_hosts_above_fleet_rejected(self, tmp_path):
        with pytest.raises(ValueError, match="elastic_min_hosts"):
            self._cfg(tmp_path, num_processes=2, data_parallel_size=2,
                      elastic_min_hosts=4)

    def test_host_spanning_model_axes_rejected(self, tmp_path):
        # dp not divisible by host count means tp/pp/cp/ep span hosts
        with pytest.raises(ValueError, match="divisible"):
            self._cfg(tmp_path, num_processes=4, data_parallel_size=6)

    def test_knob_ranges(self, tmp_path):
        for kw in (dict(ft_kill_host_at_step=-1),
                   dict(ft_host_hang_elastic=-2),
                   dict(ft_kill_host=-5),
                   dict(ft_host_hang_seconds=0.0),
                   dict(elastic_min_hosts=0),
                   dict(elastic_heartbeat_seconds=0.0),
                   dict(elastic_deadline_seconds=-1.0)):
            with pytest.raises(ValueError):
                self._cfg(tmp_path, **kw)
        # -1 is the documented "any host" sentinel for the drills
        assert self._cfg(tmp_path, ft_kill_host=-1).ft_kill_host == -1
