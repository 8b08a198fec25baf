"""The network's index of nonempty channels.

``Network`` answers ``nonempty_channels()`` and ``idle()`` from a sorted
index of its nonempty channel keys instead of scanning every channel it
ever opened. These tests hold the index to the scan it replaced: the
same deliveries in the same order under every scheduler, and an index
equal to the filtered scan after every delivery and every path that
fills or empties channels.
"""
import copy
import random

import pytest

from repro.core.messages import SYNC_KINDS, Msg
from repro.core.phaser import DistPhaser
from repro.core.runtime import (Actor, Envelope, FifoScheduler, Network,
                                PriorityScheduler, RandomScheduler)
from repro.core.skiplist import HEAD
from repro.runtime_dist.plane import COORD, PartitionedNetwork, ShardPhaser
from repro.runtime_dist.transport import InprocFabric


class ScanNetwork(Network):
    """The network as it was before the index: both questions walk
    every channel ever opened."""

    def nonempty_channels(self):
        return sorted(k for k, q in self.channels.items() if q)

    def idle(self):
        return not any(self.channels.values())


def scan(net):
    return sorted(k for k, q in net.channels.items() if q)


class CheckingRandom(RandomScheduler):
    """RandomScheduler that checks the index against the scan before
    every delivery, and so after every delivery of a run."""

    def step(self, net):
        assert net.nonempty_channels() == scan(net)
        assert net.idle() == (not scan(net))
        return super().step(net)


def churn(net, sched, *, seed, admitted, slots=16):
    """Engine-like churn on ``net``: ``slots`` live participants, and
    each step a leave and a join (sometimes only one of them) and one
    advance, until ``admitted`` participants were ever admitted."""
    rng = random.Random(seed)
    ph = DistPhaser(slots, seed=seed, net=net)
    live = list(range(slots))
    nxt = slots
    while nxt < admitted:
        r = rng.random()
        if r < 0.9 and len(live) > slots // 2:
            ph.drop(live.pop(rng.randrange(len(live))))
            ph.run(sched)
        if r > 0.1 or len(live) <= slots // 2:
            ph.async_add(min(live), nxt)
            live.append(nxt)
            nxt += 1
            ph.run(sched)
        for w in sorted(live):
            a = ph.actors[w]
            if a.sc.member and not a.sc.dropping:
                ph.signal(w)
        ph.run(sched)
        assert net.idle() and net.nonempty_channels() == []
    return ph


SCHEDULERS = {
    "fifo": FifoScheduler,
    "random": lambda: RandomScheduler(seed=2147483101),
    "priority": lambda: PriorityScheduler(SYNC_KINDS),
}


@pytest.mark.parametrize("name", sorted(SCHEDULERS))
def test_delivery_order_matches_full_scan(name):
    """Same seeded churn, about 200 participants ever admitted: the
    indexed network delivers exactly the messages the scanning network
    delivers, in the same order."""
    traces = []
    for cls in (Network, ScanNetwork):
        net = cls()
        net.trace = []
        churn(net, SCHEDULERS[name](), seed=11, admitted=200)
        traces.append((net.trace, sorted(net.channels)))
    (fast, fast_chans), (slow, slow_chans) = traces
    assert len(fast) == len(slow) > 10_000
    assert fast == slow
    assert fast_chans == slow_chans


def test_index_equals_scan_after_every_delivery():
    net = Network()
    ph = churn(net, CheckingRandom(seed=5), seed=5, admitted=60)
    ph.check_quiescent_invariants()


def test_index_empty_at_quiescence_after_thousands_of_channels():
    """Channels are never removed; the index holds only the nonempty
    ones, so at quiescence it is empty however many were opened."""
    net = Network()
    churn(net, FifoScheduler(), seed=3, admitted=360)
    assert len(net.channels) >= 2000
    assert net._ready == []
    assert net.idle() and net.nonempty_channels() == []
    # the peak since the last read is bounded by what was opened
    assert 0 < net.take_ready_peak() < len(net.channels)
    assert net.take_ready_peak() == 0


def test_nonempty_channels_is_a_copy():
    ph = DistPhaser(4, seed=0)
    for r in range(4):
        ph.signal(r)
    chans = ph.net.nonempty_channels()
    before = list(chans)
    ph.net.deliver_from(chans[0])
    assert chans == before
    ph.run()
    assert ph.net.idle()


class _Sink(Actor):
    def __init__(self, rank, net):
        super().__init__(rank, net)
        self.got = []

    def handle(self, msg):
        self.got.append(msg)


def test_ready_peak_counts_most_nonempty_channels():
    net = Network()
    sink = _Sink(0, net)
    net.register(sink)
    for src in (1, 2, 3):
        net.post(Envelope(Msg(src, 0), 1))
    net.post(Envelope(Msg(1, 0), 1))          # same channel: no new key
    assert net.nonempty_channels() == [(1, 0), (2, 0), (3, 0)]
    FifoScheduler().run(net)
    assert len(sink.got) == 4 and net.idle()
    assert net.take_ready_peak() == 3
    assert net.take_ready_peak() == 0


def test_ingest_keeps_index_coherent():
    """Remote arrivals enter the index; fenced (old generation) and
    dropped (departed destination) arrivals do not."""
    fabric = InprocFabric()
    net = PartitionedNetwork(0, fabric.endpoint(0))
    sink = _Sink(0, net)
    net.register(sink)
    net.ingest(Envelope(Msg(5, 0), 1))
    assert net.nonempty_channels() == scan(net) == [(5, 0)]
    net.ingest(Envelope(Msg(3, 0), 1, gen=7))  # fenced
    assert net.stale_gen == 1
    assert net.nonempty_channels() == scan(net) == [(5, 0)]
    net.dropped.add(0)
    net.ingest(Envelope(Msg(4, 0), 1))         # dropped destination
    assert net.black_holed == 1
    assert net.nonempty_channels() == scan(net) == [(5, 0)]
    net.dropped.clear()
    net.ingest(Envelope(Msg(2, 0), 1))
    net.ingest(Envelope(Msg(5, 0), 1))
    assert net.nonempty_channels() == scan(net) == [(2, 0), (5, 0)]
    assert net.deliver_all() == 3
    assert net.idle() and scan(net) == net.nonempty_channels() == []
    assert [(m.src, m.dst) for m in sink.got] == [(2, 0), (5, 0), (5, 0)]


def test_rebuild_clears_index_with_channels():
    """A rebuild discards the old incarnation's in-flight envelopes:
    the index empties with the channels, and the rebuilt shard runs
    phases with the index coherent."""
    fabric = InprocFabric()
    shard = ShardPhaser(COORD, fabric.endpoint(COORD), live=range(6),
                        owner_of=lambda k: COORD)
    for r in range(6):
        shard.signal(r)
    shard.net.deliver_from(shard.net.nonempty_channels()[0])
    assert shard.net.nonempty_channels() == scan(shard.net) != []
    shard.rebuild(range(5), (), phase=-1, gen=1)
    assert shard.net.channels == {} and shard.net.nonempty_channels() == []
    assert shard.net.idle()
    for phase in range(3):
        for r in range(5):
            shard.signal(r)
        assert shard.net.nonempty_channels() == scan(shard.net) != []
        shard.pump()
        assert shard.net.idle() and scan(shard.net) == []
        assert shard.actors[HEAD].head_released == phase


def test_deepcopy_copies_index():
    """The model checker forks a phaser mid-run with ``deepcopy``: each
    copy carries its own index, and both finish the same way."""
    ph = DistPhaser(6, seed=4)
    ph.net.trace = []
    for r in range(6):
        ph.signal(r)
    for _ in range(5):
        ph.net.deliver_from(ph.net.nonempty_channels()[-1])
    child = copy.deepcopy(ph)
    assert child.net.nonempty_channels() == scan(child.net) \
        == ph.net.nonempty_channels()
    assert child.net._ready is not ph.net._ready
    child.net.deliver_from(child.net.nonempty_channels()[0])
    assert ph.net.nonempty_channels() == scan(ph.net)
    assert child.net.nonempty_channels() == scan(child.net)
    ph.net.deliver_from(ph.net.nonempty_channels()[0])
    ph.run()
    child.run()
    assert ph.net.idle() and child.net.idle()
    assert ph.net.trace == child.net.trace
    assert ph.released() == child.released() == 0
