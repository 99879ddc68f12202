"""The yardstick the benchmark keeps for itself: pinned payloads, the plain
reference against the program's own writer and reader, the peak table and
the intrinsic-bytes count."""
import hashlib
import itertools

import numpy as np
import pytest

from bench import harness, reference as ref
from bench.corpus import corpus_files
from bench.payload import corpus_pool, kv_cache, sub_seeds
from bench.traffic import stream
from bench.work import write_graph_bytes

DIGESTS = harness.load_json(harness.BENCH, "digests.json")


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_corpus_bytes_are_pinned(seed):
    got = hashlib.sha256(b"".join(corpus_files(seed).values())).hexdigest()
    assert got == DIGESTS["corpus"][str(seed)]


def test_kv_payload_is_pinned():
    import jax

    cfg = harness.load_json(harness.ROOT, "bench/configs/kv-mixtral-8x7b.json")["kv_cache"]
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(kv_cache(cfg, 0)):
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == DIGESTS["kv_cache"]["0"]


def test_request_stream_is_pinned_and_deals_rounds():
    items = list(itertools.islice(stream(0, 65), 1000))
    assert hashlib.sha256(np.array(items, np.int64).tobytes()).hexdigest() == DIGESTS["stream"]["0"]
    for seed in (1, 2**33 + 7):
        first = list(itertools.islice(stream(seed, 65), 130))
        assert sorted(first[:65]) == sorted(first[65:]) == list(range(65))


def test_large_seeds_are_taken():
    seeds = sub_seeds(2**40 + 3, 7)
    assert len(set(seeds)) == 7 and all(0 <= s < 2**31 for s in seeds)


@pytest.fixture(scope="module")
def blocks():
    pool = corpus_pool(5, 1 << 20, 1)
    return [pool[i: i + 65536] for i in range(0, len(pool), 65536 * 3)]


def test_reference_writer_matches_the_programs_golden_model(blocks):
    from repro.core import compress_windowed, encode_block

    for chunk in blocks:
        want = encode_block(chunk, compress_windowed(chunk, hash_bits=8, pws=8, max_match=36).sequences)
        got = ref.encode_paper_block(chunk, hash_bits=8, pws=8, max_match=36)
        assert got == want
        assert ref.decode_block(got, len(chunk)) == chunk


def test_reference_reads_the_programs_frames(blocks):
    from repro.core import LZ4Engine

    data = b"".join(blocks[:3]) + bytes(1000)
    frame = LZ4Engine(micro_batch=4).compress(data)
    info = ref.parse_frame(frame)
    assert info["version"] == 3 and info["content_size"] == len(data)
    out = b"".join(b["payload"] if b["raw"] else ref.decode_block(b["payload"], b["usize"])
                   for b in info["blocks"])
    assert out == data
    assert [b["crc"] for b in info["blocks"]] == [
        ref.crc32(data[i: i + 65536]) for i in range(0, len(data), 65536)]
    assert write_graph_bytes([(0, frame)]) == len(data) + sum(b["csize"] for b in info["blocks"])


def test_reference_decoder_refuses_damage():
    with pytest.raises(ref.FormatError):
        ref.decode_block(bytes([0x10, 65]), 2)  # one literal, table says two
    with pytest.raises(ref.FormatError):
        ref.decode_block(bytes([0x10, 65, 9, 0]), 10)  # offset before the block


def test_peaks_are_keyed_by_device_kind():
    assert harness.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError):
        harness.peaks("TPU v99")


@pytest.mark.parametrize("cut", [0, 3, 16, 20])
def test_reference_refuses_a_truncated_frame(cut, blocks):
    from repro.core import LZ4Engine

    frame = LZ4Engine(micro_batch=2).compress(blocks[0])
    with pytest.raises(ref.FormatError):
        ref.parse_frame(frame[:cut])
