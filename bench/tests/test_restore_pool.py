"""The restore cell's pool is one for every run: the mix's ``pool_seed``
draws it, and the run's seed only deals the order of the requests and picks
the frame and block the integrity probe damages."""
import copy
import hashlib

import jax
import pytest

from bench import harness, loops, ops
from bench.payload import corpus_pool

SPEC = harness.load_json(harness.ROOT, "BENCHMARK.json")
DIGESTS = harness.load_json(harness.BENCH, "digests.json")
SEEDS = (2**33 + 17, 1600000101)


def restore_cell() -> tuple[dict, dict]:
    _, cfg, mix = harness.cell(SPEC, "calgary.restore")
    return copy.deepcopy(cfg), copy.deepcopy(mix)


@pytest.fixture(scope="module")
def tiny_ops() -> dict:
    """The restore op at a test size, set up once for each run seed: four
    frames of two blocks."""
    cfg, mix = restore_cell()
    cfg["payload"].update(pool_bytes=8 * 65536, corpus_seeds=1)
    cfg["engine"]["micro_batch"] = 2
    mix["frame_bytes"] = 2 * 65536
    out = {}
    for seed in SEEDS:
        op = ops.load(mix["op"])(cfg, mix, seed, jax.devices()[:1])
        op.build()
        out[seed] = (op, op.ready(op.make_payload()))
    return out


def test_pool_and_frames_are_the_same_for_every_run_seed(tiny_ops):
    (a, n_a), (b, n_b) = tiny_ops.values()
    assert n_a == n_b == 4
    assert a.pool == b.pool
    assert a.frames == b.frames


class Items:
    """Stands in for the op in the loop and keeps the items it is sent."""

    def __init__(self):
        self.items = []

    def request(self, item: int) -> int:
        self.items.append(item)
        return 1


def test_request_order_follows_the_run_seed(tiny_ops):
    def order(seed: int) -> list[int]:
        op, n = tiny_ops[seed]
        rec = Items()
        loops.load(op.mix["loop"]).window(rec, op.mix, seed, n, 60.0, 3 * n)
        return rec.items

    orders = {seed: order(seed) for seed in SEEDS}
    for items in orders.values():
        assert sorted(items) == sorted(3 * list(range(4)))
    assert orders[SEEDS[0]] != orders[SEEDS[1]]
    assert order(SEEDS[0]) == orders[SEEDS[0]]


def test_probe_frame_and_block_follow_the_run_seed(tiny_ops, monkeypatch):
    from bench.ops import restore

    picks = []

    def corrupt(frame: bytes, block: int, at: int = 0) -> bytes:
        picks.append((frame, block))
        return ops.corrupt(frame, block, at)

    monkeypatch.setattr(restore, "corrupt", corrupt)
    probe = {}
    for seed in SEEDS + SEEDS:
        op, _ = tiny_ops[seed]
        assert op.check()["corrupt_accepted"]["value"] == 0
        frame, block = picks[-1]
        pick = (op.frames.index(frame), block)
        assert probe.setdefault(seed, pick) == pick
    assert probe[SEEDS[0]] != probe[SEEDS[1]]


def test_restore_pool_is_pinned():
    cfg, mix = restore_cell()
    op = ops.load(mix["op"])(cfg, mix, SEEDS[0], [])
    pool = op.make_payload()
    assert pool == corpus_pool(mix["pool_seed"], cfg["payload"]["pool_bytes"],
                               cfg["payload"]["corpus_seeds"])
    assert len(pool) == 8 << 20
    want = DIGESTS["restore_pool"][str(mix["pool_seed"])]
    assert hashlib.sha256(pool).hexdigest() == want
