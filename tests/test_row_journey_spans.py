"""What the program's spans say of a row's place (PR 36): a row's position is
its 1-based index in the stream of rows pushed into host staging, and every
span it passes says it, so a trace reader can follow the row from
``ingest.admit`` to the chunk that first samples it. CPU, tiny sizes, the
recording annotator of ``test_program_spans``."""

import numpy as np
import pytest

from test_program_spans import (ACT, BLOCK, CAP, OBS, build_plane,
                                recorder, rows)

__all__ = ["recorder"]  # the recording annotator, as a fixture


@pytest.fixture
def plane(rng, recorder):
    """A fused loop over a service, the seeded fill (positions 1..2 *
    BLOCK, what the two-block staging ring holds) already on the device."""
    loop, service, buf, state = build_plane(rng, fill=2 * BLOCK)
    state, _m = loop.run(state, 2)  # compiles; nothing staged
    recorder.spans.clear()
    yield loop, service, buf, state
    loop.close()
    service.close()


def named(rec, name):
    return [s for s in rec.spans if s.name == name]


def block_of(rec, position):
    """The ``fused.stage_block`` whose positions hold ``position``."""
    hit = [b for b in named(rec, "fused.stage_block")
           if b.stats["first"] <= position <= b.stats["through"]]
    assert len(hit) <= 1
    return hit[0] if hit else None


def test_positions_are_contiguous_across_blocks_and_groups(
        recorder, plane, rng):
    loop, service, buf, state = plane
    base = buf.staged_position()[0]
    sizes = [5, BLOCK - 4, 3, 6, 4]  # two blocks, inside the two-block ring
    for i, n in enumerate(sizes):
        assert service.add(rows(rng, n, first=100 * i))
        service.flush()  # a group an add: each span is one ticket's
    state, _m = loop.run(state, 12)
    hosts = named(recorder, "ingest.host_stage")
    assert [h.stats["rows"] for h in hosts] == sizes
    # a push says where its last row stands: the running row count
    assert [h.stats["through"] for h in hosts] == \
        (base + np.cumsum(sizes)).tolist()
    admits = named(recorder, "ingest.admit")
    assert [a.stats["seq"] for a in admits] == \
        [h.stats["seq_lo"] for h in hosts] == \
        [h.stats["seq_hi"] for h in hosts]
    blocks = named(recorder, "fused.stage_block")
    assert blocks[0].stats["first"] == base + 1
    for prev, nxt in zip(blocks, blocks[1:]):
        assert nxt.stats["first"] == prev.stats["through"] + 1
        assert nxt.stats["block"] == prev.stats["block"] + 1
    for b in blocks:
        assert b.stats["through"] - b.stats["first"] + 1 == b.stats["rows"]
    assert blocks[-1].stats["through"] == base + sum(sizes)
    # every block's commit says the same last position under the same id
    commits = {c.stats["block"]: c for c in named(recorder,
                                                  "fused.commit_staged")}
    for b in blocks:
        assert commits[b.stats["block"]].stats["through"] \
            == b.stats["through"]
    # and every add is in exactly one block
    for h in hosts:
        assert block_of(recorder, h.stats["through"]) is not None


def test_a_group_that_straddles_a_block_boundary_is_followed_to_the_later(
        recorder, plane, rng):
    loop, service, buf, state = plane
    base = buf.staged_position()[0]
    assert service.add(rows(rng, BLOCK + BLOCK // 2, first=500))
    service.flush()
    state, _m = loop.run(state, 8)
    (host,) = named(recorder, "ingest.host_stage")
    first, second = named(recorder, "fused.stage_block")
    assert first.stats["rows"] == BLOCK and second.stats["rows"] == BLOCK // 2
    # the journey is that of the group's last row: it rides the second block
    assert host.stats["through"] == base + BLOCK + BLOCK // 2
    assert block_of(recorder, host.stats["through"]) is second
    assert not (first.stats["first"] <= host.stats["through"]
                <= first.stats["through"])


def test_a_dropped_row_is_in_no_block_and_is_counted(recorder, plane, rng):
    loop, service, buf, state = plane
    base = buf.staged_position()[0]
    # three blocks into a two-block ring before the learner stages any
    for i in range(3):
        assert service.add(rows(rng, BLOCK, first=1000 * (i + 1)))
        service.flush()
    state, _m = loop.run(state, 10)
    hosts = named(recorder, "ingest.host_stage")
    assert [h.stats["dropped"] for h in hosts] == [0, 0, BLOCK]
    assert [h.stats["through"] for h in hosts] == [
        base + BLOCK, base + 2 * BLOCK, base + 3 * BLOCK]
    blocks = named(recorder, "fused.stage_block")
    # the first add's rows were dropped: no block carries its position, and
    # the blocks start past it
    assert block_of(recorder, hosts[0].stats["through"]) is None
    assert min(b.stats["first"] for b in blocks) == base + BLOCK + 1
    assert block_of(recorder, hosts[1].stats["through"]) is blocks[0]
    assert block_of(recorder, hosts[2].stats["through"]) is blocks[1]
    assert sum(b.stats["rows"] for b in blocks) == 2 * BLOCK
    assert buf.staged_position() == (base + 3 * BLOCK, BLOCK)


def test_landed_never_exceeds_what_was_committed(recorder, plane, rng):
    loop, service, buf, state = plane
    fed = []

    def feed(_state, _k):  # rows keep arriving between chunks
        if len(fed) < 4:
            fed.append(service.add(rows(rng, BLOCK - 3, first=len(fed))))
            service.flush()

    committed = buf.landed  # what the fill's drain landed
    state, _m = loop.run(state, 16, on_chunk=feed)
    seen = []
    for s in recorder.spans:
        if s.name == "fused.commit_staged":
            committed = s.stats["through"]
        elif s.name == "learner.dispatch":
            # exactly what the commits dispatched before this chunk landed
            seen.append((s.stats["landed"], committed))
    assert len(seen) == 8
    assert all(landed == done for landed, done in seen)
    landed = [x for x, _ in seen]
    assert landed == sorted(landed) and landed[-1] > landed[0]
    assert landed[-1] == buf.landed <= buf.staged_position()[0]


def test_the_multi_ring_merge_keeps_positions_in_ticket_order(recorder, rng):
    from d4pg_tpu.replay.fused_buffer import FusedDeviceReplay

    buf = FusedDeviceReplay(4 * CAP, OBS, ACT, alpha=0.6, block_rows=BLOCK,
                            staging_blocks=4, ingest_shards=2)
    # tickets interleaved over the two shards, each push tagged by its
    # ticket in `done`; quiescent before the learner merges
    sizes = [6, 10, 4, 12, 9, 7]
    for ticket, n in enumerate(sizes):
        buf.add_sharded(rows(rng, n, first=1000 * ticket),
                        shard=ticket % 2, ticket=ticket)
    assert buf.staged_position() == (sum(sizes), 0)
    merged = []
    while True:
        views, n = buf._staging.frame()
        if n == 0:
            break
        merged.append(np.array(views.done[:n]))
        assert buf.stage_block() == n and buf.commit_staged() == n
    tickets = (np.concatenate(merged) // 1000).astype(int)
    assert tickets.tolist() == sorted(tickets.tolist())  # ticket order
    assert np.bincount(tickets).tolist() == sizes
    blocks = named(recorder, "fused.stage_block")
    assert [b.stats["first"] for b in blocks] == [1, BLOCK + 1, 2 * BLOCK + 1]
    assert blocks[-1].stats["through"] == sum(sizes) == buf.landed
    # a block says the tickets whose LAST row it carries, in order and once
    ends = np.cumsum(sizes)
    said = []
    for b in blocks:
        want = [t for t, e in enumerate(ends)
                if b.stats["first"] <= e <= b.stats["through"]]
        assert (b.stats["seq_lo"], b.stats["seq_hi"]) == (want[0], want[-1])
        said += want
    assert said == list(range(len(sizes)))
    # tickets of the ring's own making (``add`` on the commit thread) tie
    # nothing: the position does, and the block says no ticket
    buf.add(rows(rng, 5))
    assert buf.staged_position()[0] == sum(sizes) + 5
    recorder.spans.clear()
    assert buf.stage_block() == 5
    (b,) = named(recorder, "fused.stage_block")
    assert "seq_lo" not in b.stats and b.stats["through"] == sum(sizes) + 5
    assert buf.commit_staged() == 5
    # and a caller's ticket after them is said again (a seeded fill through
    # ``add`` does not silence the direct stage that follows it)
    buf.add(rows(rng, 3))
    buf.add_sharded(rows(rng, 4), shard=1, ticket=100)
    recorder.spans.clear()
    assert buf.stage_block() == 7
    (b,) = named(recorder, "fused.stage_block")
    assert (b.stats["seq_lo"], b.stats["seq_hi"]) == (100, 100)
