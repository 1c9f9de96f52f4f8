"""Parallel ingest tests: the multi-process store is byte-identical to the
serial reference path, and the single-writer manifest merge refuses
ambiguous (overlapping/gappy) worker output."""
import functools
import json
import os

import numpy as np
import pytest

from repro.data import SyntheticConfig, ingest_synthetic
from repro.data.ingest import ingest_chunks, merge_shard_groups
from repro.data.synthetic import chunk_sizes, synthesize_chunk

CFG = SyntheticConfig(n_sessions=700, n_queries=12, docs_per_query=8,
                      positions=6, behavior="dbn", seed=17)
SPLITS = {"train": 0.8, "val": 0.1, "test": 0.1}


def tree_bytes(root):
    out = {}
    for dirpath, _, files in os.walk(root):
        for fn in files:
            p = os.path.join(dirpath, fn)
            with open(p, "rb") as f:
                out[os.path.relpath(p, root)] = f.read()
    return out


def assert_trees_identical(a, b):
    """Byte-identical store trees; manifests may differ ONLY in the
    recorded ``metadata.ingest_workers``."""
    ta, tb = tree_bytes(a), tree_bytes(b)
    assert set(ta) == set(tb)
    for rel in sorted(ta):
        if os.path.basename(rel) == "manifest.json":
            ma, mb = json.loads(ta[rel]), json.loads(tb[rel])
            ma["metadata"].pop("ingest_workers", None)
            mb["metadata"].pop("ingest_workers", None)
            assert ma == mb, rel
        else:
            assert ta[rel] == tb[rel], rel


def test_parallel_ingest_bit_identical_to_serial(tmp_path):
    """The pin: 3 spawn workers over ragged shard blocks produce the same
    shard files and manifests (modulo the recorded worker count) as the
    single-process reference, split routing included."""
    serial = ingest_synthetic(CFG, str(tmp_path / "w1"), chunk_sessions=150,
                              shard_rows=120, splits=SPLITS, codec="auto",
                              workers=1)
    par = ingest_synthetic(CFG, str(tmp_path / "w3"), chunk_sessions=150,
                           shard_rows=120, splits=SPLITS, codec="auto",
                           workers=3)
    assert_trees_identical(tmp_path / "w1", tmp_path / "w3")
    for name, store in par.items():
        store.verify()
        assert store.metadata["ingest_workers"] == 3
        assert store.metadata["store_codec"] == "auto"
        assert serial[name].metadata["ingest_workers"] == 1
        a, b = serial[name].read_all(), store.read_all()
        for k in a:
            np.testing.assert_array_equal(a[k], b[k], err_msg=(name, k))
    # compression actually engaged on the 0/1 columns
    assert par["train"].shard_codec(0, "clicks") == "bitpack"
    assert par["train"].shard_codec(0, "mask") == "bitpack"


def test_ingest_chunks_no_splits_parallel_raw(tmp_path):
    rows = chunk_sizes(CFG, 200)
    fn = functools.partial(synthesize_chunk, CFG, chunk_sessions=200)
    one = ingest_chunks(fn, rows, str(tmp_path / "w1"), shard_rows=150,
                        codec="raw", workers=1, seed=CFG.seed)[""]
    two = ingest_chunks(fn, rows, str(tmp_path / "w2"), shard_rows=150,
                        codec="raw", workers=2, seed=CFG.seed)[""]
    assert_trees_identical(tmp_path / "w1", tmp_path / "w2")
    assert one.rows == two.rows == CFG.n_sessions
    # raw codec keeps the zero-copy memmap read path
    assert isinstance(two.open_shard(0)["clicks"], np.memmap)


def test_more_workers_than_shards(tmp_path):
    """Workers whose shard block is empty contribute nothing; the merged
    store is still complete and identical to serial."""
    cfg = SyntheticConfig(n_sessions=120, n_queries=8, docs_per_query=6,
                          positions=4, behavior="pbm", seed=5)
    ingest_synthetic(cfg, str(tmp_path / "w1"), chunk_sessions=50,
                     shard_rows=100, workers=1)
    many = ingest_synthetic(cfg, str(tmp_path / "w4"), chunk_sessions=50,
                            shard_rows=100, workers=4)
    assert_trees_identical(tmp_path / "w1", tmp_path / "w4")
    assert many[""].rows == 120 and many[""].n_shards == 2


def test_ingest_workers_initialize_no_jax_backend(tmp_path, monkeypatch):
    """A worker shares its host with the process that holds the chip: had
    it initialized a JAX backend, it would claim the device. Spawned under
    a platform no backend serves, any such initialization raises."""
    monkeypatch.setenv("JAX_PLATFORMS", "no_such_platform")
    store = ingest_synthetic(CFG, str(tmp_path / "s"), chunk_sessions=200,
                             shard_rows=300, splits=SPLITS, workers=2)
    assert sum(s.rows for s in store.values()) == CFG.n_sessions


def _entry(i, rows=10):
    return {"name": f"shard_{i:05d}", "rows": rows}


def test_merge_shard_groups_orders_and_validates():
    merged = merge_shard_groups([[_entry(2)], [_entry(0), _entry(1)]])
    assert [e["name"] for e in merged] == [f"shard_{i:05d}" for i in range(3)]
    with pytest.raises(ValueError, match="overlapping shard groups"):
        merge_shard_groups([[_entry(0)], [_entry(0)]])
    with pytest.raises(ValueError, match="gaps"):
        merge_shard_groups([[_entry(0)], [_entry(2)]])
    with pytest.raises(ValueError, match="no shards"):
        merge_shard_groups([[], []])


def test_ingest_chunks_matches_concatenated_chunks(tmp_path):
    rows = [7, 7, 7, 4]
    fn = lambda c: {"x": np.arange(rows[c], dtype=np.int64)[:, None] + 100 * c,
                    "y": np.full((rows[c],), c, np.int32)}
    store = ingest_chunks(fn, rows, str(tmp_path / "s"), shard_rows=10,
                          codec="auto", workers=1)[""]
    store.verify()
    got = store.read_all()
    np.testing.assert_array_equal(
        got["x"], np.concatenate([np.arange(n, dtype=np.int64)[:, None]
                                  + 100 * c for c, n in enumerate(rows)]))
    np.testing.assert_array_equal(
        got["y"], np.concatenate([np.full(n, c, np.int32)
                                  for c, n in enumerate(rows)]))


def test_ingest_chunks_validation(tmp_path):
    fn = lambda c: {"x": np.zeros((10, 2), np.float32)}
    with pytest.raises(ValueError, match="codec"):
        ingest_chunks(fn, [10], str(tmp_path / "a"), codec="zstd")
    with pytest.raises(ValueError, match="workers"):
        ingest_chunks(fn, [10], str(tmp_path / "b"), workers=0)
    with pytest.raises(ValueError, match="chunk_rows"):
        ingest_chunks(fn, [], str(tmp_path / "c"))
    with pytest.raises(ValueError, match="zero rows"):
        ingest_chunks(fn, [10], str(tmp_path / "d"),
                      splits={"train": 0.99, "val": 0.01})
    # a chunk_fn that disagrees with the plan is a hard error, not bad bytes
    with pytest.raises(ValueError, match="deterministic in the chunk index"):
        ingest_chunks(fn, [10, 12], str(tmp_path / "e"), workers=1)
    # nothing above may have committed a manifest
    for sub in ("a", "b", "c", "d", "e"):
        assert not os.path.exists(tmp_path / sub / "manifest.json")
