"""Streaming record input: indexed record shards + parallel decode +
checkpointable iterators (ROADMAP item 5).

Contracts pinned here:
- write_records/RecordSource round-trip variable-length records exactly;
  empty records are rejected at write time.
- Corruption is LOUD: CRC mismatch and truncation raise
  RecordCorruptionError naming the shard file and record index.
- The batch stream is bit-identical for ANY decode_workers count
  (including 0 = inline), and matches the in-memory Pipeline over the
  decoded rows (same seeded permutation).
- Pipeline.state_dict()/load_state() make mid-epoch checkpoint resume
  bit-equal to an uninterrupted run — across DIFFERENT worker counts —
  and the checkpoint meta carries the cursor automatically.
- Sharded record pipelines compose with reshard: host slices assemble
  into exactly the unsharded batch, before and after a resize.

Shapes are lean (tier-1 budget); what decode parallelism gains on a
decode-bound input is not measured on the chip.
"""

import os
import zlib

import numpy as np
import pytest

import distributed_tpu as dtpu
from distributed_tpu.data import (
    Pipeline,
    RecordCorruptionError,
    RecordSource,
    write_records,
)

ROW_SHAPE = (4, 3)


def _make_records(tmp_path, n=100, records_per_shard=17, seed=0,
                  labels=True, name="recs"):
    """Variable-length records: [label byte][12 row bytes][random pad]."""
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, 256, (n,) + ROW_SHAPE, dtype=np.uint8)
    recs = []
    for i in range(n):
        pad = bytes(rng.integers(0, 256, int(rng.integers(0, 40))).tolist())
        label = bytes([i % 256]) if labels else b"\xff"
        recs.append(label + rows[i].tobytes() + pad)
    d = tmp_path / name
    write_records(d, recs, records_per_shard=records_per_shard)
    return d, rows, recs


def _decode(b):
    row = np.frombuffer(b[1:13], np.uint8).reshape(ROW_SHAPE)
    return row.astype(np.float32), b[0]


def _decode_unlabeled(b):
    return np.frombuffer(b[1:13], np.uint8).reshape(ROW_SHAPE)


def _tiny_classifier(width=16):
    """Flatten->Dense stack: the cheapest model that can learn the synthetic
    labels — these tests pin STREAM semantics, not model quality."""
    return dtpu.nn.Sequential([
        dtpu.nn.Flatten(),
        dtpu.nn.Dense(width, activation="relu"),
        dtpu.nn.Dense(10),
    ])


class TestRecordFormat:
    def test_round_trip_variable_lengths(self, tmp_path):
        d, _, recs = _make_records(tmp_path)
        with RecordSource(d) as src:
            assert len(src) == 100
            lengths = {len(src.read(i)) for i in range(100)}
            assert len(lengths) > 1  # genuinely variable-length
            for i in (0, 16, 17, 50, 99):  # crosses shard boundaries
                assert src.read(i) == recs[i]

    def test_empty_record_rejected(self, tmp_path):
        with pytest.raises(ValueError, match="empty"):
            write_records(tmp_path / "e", [b"ok", b""])

    def test_existing_shards_rejected(self, tmp_path):
        d, _, _ = _make_records(tmp_path)
        with pytest.raises(FileExistsError):
            write_records(d, [b"x"])

    def test_missing_sidecar_index_is_loud(self, tmp_path):
        d, _, _ = _make_records(tmp_path)
        (d / "records-00001-idx.npy").unlink()
        with pytest.raises(FileNotFoundError, match="records-00001-idx"):
            RecordSource(d)

    def test_crc_corruption_names_shard_and_record(self, tmp_path):
        d, _, _ = _make_records(tmp_path)
        path = d / "records-00001.drs"
        data = bytearray(path.read_bytes())
        data[-1] ^= 0xFF  # flip a payload byte of the shard's last record
        path.write_bytes(bytes(data))
        with RecordSource(d) as src:
            with pytest.raises(RecordCorruptionError,
                               match=r"records-00001\.drs.*record 16"):
                src.read(17 + 16)  # last record of shard 1

    def test_truncation_names_shard_and_record(self, tmp_path):
        d, _, _ = _make_records(tmp_path)
        path = d / "records-00001.drs"
        with open(path, "r+b") as f:
            f.truncate(10)
        with RecordSource(d) as src:
            with pytest.raises(RecordCorruptionError,
                               match=r"records-00001\.drs is truncated"):
                src.read(18)

    def test_bad_magic_rejected(self, tmp_path):
        d, _, _ = _make_records(tmp_path)
        path = d / "records-00000.drs"
        data = bytearray(path.read_bytes())
        data[:4] = b"JUNK"
        path.write_bytes(bytes(data))
        with pytest.raises(RecordCorruptionError, match="magic"):
            RecordSource(d)

    def test_decode_and_probe(self, tmp_path):
        d, rows, _ = _make_records(tmp_path)
        src = RecordSource(d, decode_fn=_decode)
        assert src.probe() == (ROW_SHAPE, True)
        row, label = src.decode(42)
        np.testing.assert_array_equal(row, rows[42].astype(np.float32))
        assert label == 42


class TestDecodePipeline:
    @pytest.mark.parametrize("workers", [1, 4])
    def test_stream_bit_identical_across_worker_counts(self, tmp_path,
                                                       workers):
        d, _, _ = _make_records(tmp_path)
        with Pipeline(RecordSource(d, decode_fn=_decode), None, 10,
                      seed=3) as p0, \
             Pipeline(RecordSource(d, decode_fn=_decode), None, 10,
                      seed=3, decode_workers=workers) as pw:
            assert p0.decode_workers == 0
            for _ in range(25):  # crosses pass boundaries (reshuffles)
                xa, ya = next(p0)
                xb, yb = next(pw)
                np.testing.assert_array_equal(xa, xb)
                np.testing.assert_array_equal(ya, yb)

    def test_matches_in_memory_stream(self, tmp_path):
        """Decoded record stream == the in-memory Pipeline over the same
        rows: one seeded permutation addresses every source format."""
        d, rows, _ = _make_records(tmp_path, n=96, records_per_shard=13)
        labels = np.arange(96, dtype=np.int32)
        with Pipeline(RecordSource(d, decode_fn=_decode), None, 16,
                      seed=7, decode_workers=2) as rec, \
             Pipeline(rows, labels, 16, seed=7, use_native=False,
                      scale=1.0) as mem:
            for _ in range(12):
                xa, ya = next(rec)
                xb, yb = next(mem)
                np.testing.assert_array_equal(xa, xb)
                np.testing.assert_array_equal(ya % 256, yb % 256)

    def test_unlabeled_decode_and_seek(self, tmp_path):
        d, rows, _ = _make_records(tmp_path, labels=False)
        with Pipeline(RecordSource(d, decode_fn=_decode_unlabeled), None,
                      10, seed=5, decode_workers=2) as p:
            for _ in range(7):
                next(p)
            want = [next(p) for _ in range(3)]
        with Pipeline(RecordSource(d, decode_fn=_decode_unlabeled), None,
                      10, seed=5, decode_workers=3) as q:
            q.seek(7)
            for wx, wy in want:
                gx, gy = next(q)
                np.testing.assert_array_equal(wx, gx)
                np.testing.assert_array_equal(wy, gy)  # zeros, but aligned

    def test_decode_error_surfaces_with_original_type(self, tmp_path):
        d, _, _ = _make_records(tmp_path)
        path = d / "records-00002.drs"
        data = bytearray(path.read_bytes())
        data[-1] ^= 0xFF
        path.write_bytes(bytes(data))
        with Pipeline(RecordSource(d, decode_fn=_decode), None, 100,
                      seed=0, shuffle=False, decode_workers=2) as p:
            with pytest.raises(RecordCorruptionError,
                               match=r"records-00002\.drs"):
                next(p)

    def test_decode_workers_require_records(self, tmp_path):
        x = np.zeros((32, 4, 3), np.uint8)
        with pytest.raises(ValueError, match="decode_workers"):
            Pipeline(x, None, 8, decode_workers=2)

    def test_record_source_requires_decode_fn(self, tmp_path):
        d, _, _ = _make_records(tmp_path)
        with pytest.raises(ValueError, match="decode_fn"):
            Pipeline(RecordSource(d), None, 8)

    def test_use_native_rejected_for_records(self, tmp_path):
        d, _, _ = _make_records(tmp_path)
        with pytest.raises(ValueError, match="use_native"):
            Pipeline(RecordSource(d, decode_fn=_decode), None, 8,
                     use_native=True)

    def test_labels_from_decode_exclude_y(self, tmp_path):
        d, _, _ = _make_records(tmp_path)
        with pytest.raises(ValueError, match="decode_fn"):
            Pipeline(RecordSource(d, decode_fn=_decode),
                     np.zeros(100, np.int32), 8)


class TestIteratorState:
    def test_state_dict_round_trip(self, tmp_path):
        d, _, _ = _make_records(tmp_path)

        def pipe(w):
            return Pipeline(RecordSource(d, decode_fn=_decode), None, 10,
                            seed=3, decode_workers=w)

        with pipe(2) as a:
            for _ in range(13):
                next(a)
            state = a.state_dict()
            assert state["steps_emitted"] == 13
            assert state["pass"] == 1 and state["step_in_pass"] == 3
            want = [next(a) for _ in range(3)]
        with pipe(4) as b:  # different worker count on resume
            b.load_state(state)
            for wx, wy in want:
                gx, gy = next(b)
                np.testing.assert_array_equal(wx, gx)
                np.testing.assert_array_equal(wy, gy)

    def test_load_state_validates_stream_identity(self, tmp_path):
        d, _, _ = _make_records(tmp_path)
        with Pipeline(RecordSource(d, decode_fn=_decode), None, 10,
                      seed=3) as p:
            state = p.state_dict()
        with Pipeline(RecordSource(d, decode_fn=_decode), None, 10,
                      seed=4) as q:
            with pytest.raises(ValueError, match="seed"):
                q.load_state(state)
        with Pipeline(RecordSource(d, decode_fn=_decode), None, 20,
                      seed=3) as q:
            with pytest.raises(ValueError, match="batch_size"):
                q.load_state(state)

    def test_consumed_steps_overrides_staged_ahead_cursor(self, tmp_path):
        d, _, _ = _make_records(tmp_path)
        with Pipeline(RecordSource(d, decode_fn=_decode), None, 10,
                      seed=3) as p:
            for _ in range(9):  # source staged ahead of the trained step
                next(p)
            state = p.state_dict(consumed_steps=6)
            assert state["steps_emitted"] == 6

    def test_mid_epoch_resume_bit_equal(self, tmp_path):
        """The acceptance pin: interrupt mid-epoch, resume from the
        checkpoint (which carries the iterator cursor) with a DIFFERENT
        decode worker count, finish bit-identical to uninterrupted."""
        from distributed_tpu.training.callbacks import ModelCheckpoint

        import jax

        d, _, _ = _make_records(tmp_path, n=256, records_per_shard=60,
                                name="img")

        def decode(b):
            row = np.frombuffer(b[1:13], np.uint8).reshape(4, 3, 1)
            return row.astype(np.float32) / 255.0, b[0] % 10

        def make_model():
            m = dtpu.Model(_tiny_classifier())
            m.compile(optimizer=dtpu.optim.SGD(0.05),
                      loss="sparse_categorical_crossentropy")
            m.build((4, 3, 1), seed=0)
            return m

        def pipe(w):
            return Pipeline(RecordSource(d, decode_fn=decode), None, 64,
                            seed=8, decode_workers=w)

        with pipe(0) as p1:
            m1 = make_model()
            m1.fit(p1, epochs=3, verbose=0)

        class StopAt(dtpu.callbacks.Callback):
            def on_batch_end(self, model, step, logs):
                if step == 6:  # mid-epoch-2 (4 steps/pass)
                    model.stop_training = True

        ckdir = tmp_path / "ck"
        with pipe(2) as p2:
            m2 = make_model()
            m2.fit(p2, epochs=3, verbose=0,
                   callbacks=[ModelCheckpoint(ckdir, save_freq=2),
                              StopAt()])
        assert m2.step == 6
        with pipe(4) as p3:
            m3 = make_model()
            m3.fit(p3, epochs=3, verbose=0,
                   callbacks=[ModelCheckpoint(ckdir, save_freq=2,
                                              restore=True)])
        assert m3.step == m1.step
        for a, b in zip(jax.tree_util.tree_leaves(m1.params),
                        jax.tree_util.tree_leaves(m3.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_checkpoint_meta_carries_data_state(self, tmp_path):
        from distributed_tpu.checkpoint import Checkpointer, load_npz
        from distributed_tpu.training.callbacks import ModelCheckpoint

        d, _, _ = _make_records(tmp_path, n=128, name="img2")

        def decode(b):
            row = np.frombuffer(b[1:13], np.uint8).reshape(4, 3, 1)
            return row.astype(np.float32), b[0] % 10

        m = dtpu.Model(_tiny_classifier(8))
        m.compile(optimizer=dtpu.optim.SGD(0.05),
                  loss="sparse_categorical_crossentropy")
        m.build((4, 3, 1), seed=0)
        ckdir = tmp_path / "ck2"
        with Pipeline(RecordSource(d, decode_fn=decode), None, 32,
                      seed=1) as p:
            m.fit(p, epochs=1, verbose=0,
                  callbacks=[ModelCheckpoint(ckdir, save_freq="epoch")])
        step = Checkpointer(ckdir).latest_step()
        _, meta = load_npz(ckdir / f"ckpt-{step}.npz")
        assert meta["data_state"]["steps_emitted"] == step
        assert meta["data_state"]["seed"] == 1
        assert meta["data_state"]["batch_size"] == 32


class TestReshardComposition:
    def test_sharded_streams_assemble_and_survive_resize(self, tmp_path):
        """Record-source shards of the global stream concatenate into the
        unsharded batch; a reshard mid-stream (the elastic primitive)
        keeps the assembled stream identical."""
        d, _, _ = _make_records(tmp_path, n=96, records_per_shard=20)

        def pipe(shard=None, w=2):
            return Pipeline(RecordSource(d, decode_fn=_decode), None, 12,
                            seed=4, shard=shard, decode_workers=w)

        with pipe() as full:
            stream = [next(full) for _ in range(10)]
        parts = [pipe(shard=(i, 2)) for i in range(2)]
        try:
            for step in range(4):
                fx, fy = stream[step]
                px = np.concatenate([next(p)[0] for p in parts])
                np.testing.assert_array_equal(fx, px)
            # Elastic resize 2 -> 3 at step 4: new slices of the SAME
            # global stream, cursor preserved.
            for p in parts:
                p.close()
            parts = [pipe(shard=(i, 3), w=1) for i in range(3)]
            for p in parts:
                p.seek(4)
            for step in range(4, 8):
                fx, fy = stream[step]
                px = np.concatenate([next(p)[0] for p in parts])
                np.testing.assert_array_equal(fx, px)
        finally:
            for p in parts:
                p.close()

    def test_reshard_in_place_drops_stale_decodes(self, tmp_path):
        d, _, _ = _make_records(tmp_path, n=96, records_per_shard=20)
        with Pipeline(RecordSource(d, decode_fn=_decode), None, 12,
                      seed=4, decode_workers=3) as p, \
             Pipeline(RecordSource(d, decode_fn=_decode), None, 12,
                      seed=4, shard=(1, 2), decode_workers=3) as ref:
            for _ in range(5):
                next(p)  # pool has staged shard-(0,1) slices ahead
                next(ref)
            p.reshard((1, 2))
            for _ in range(4):
                xa, ya = next(p)
                xb, yb = next(ref)
                np.testing.assert_array_equal(xa, xb)
                np.testing.assert_array_equal(ya, yb)


@pytest.mark.slow
def test_heavy_decode_matrix_bit_identical(tmp_path):
    """Heavier determinism matrix (@slow — tier-1 keeps the lean shapes):
    W in {0, 1, 2, 4, 8} x sharded/unsharded over a multi-pass stream,
    with a genuinely costly decode_fn, every stream bit-identical to
    W=0 unsharded."""
    d, _, _ = _make_records(tmp_path, n=480, records_per_shard=37)

    def costly_decode(b):
        raw = b[1:13]
        acc = zlib.crc32(b * 50)  # real per-record CPU work
        row = np.frombuffer(raw, np.uint8).reshape(ROW_SHAPE)
        return row.astype(np.float32) + np.float32((acc % 7) * 0.0), b[0]

    def pipe(w, shard=None):
        return Pipeline(RecordSource(d, decode_fn=costly_decode), None, 24,
                        seed=11, shard=shard, decode_workers=w)

    with pipe(0) as ref:
        stream = [next(ref) for _ in range(50)]  # 2.5 passes
    for w in (1, 2, 4, 8):
        with pipe(w) as p:
            for step in range(50):
                xb, yb = next(p)
                np.testing.assert_array_equal(stream[step][0], xb)
                np.testing.assert_array_equal(stream[step][1], yb)
    for w in (2, 8):
        parts = [pipe(w, shard=(i, 3)) for i in range(3)]
        try:
            for step in range(12):
                px = np.concatenate([next(p)[0] for p in parts])
                np.testing.assert_array_equal(stream[step][0], px)
        finally:
            for p in parts:
                p.close()


def test_fit_trains_from_record_pipeline(tmp_path):
    """End to end: model.fit over a record-backed streaming pipeline with
    parallel decode learns separable synthetic data."""
    x, y = dtpu.data.synthetic_images(256, (8, 8), 10, seed=5)
    d = tmp_path / "imgs"
    write_records(
        d,
        (bytes([int(l)]) + zlib.compress(img.tobytes())
         for img, l in zip(x[..., None], y)),
        records_per_shard=100,
    )

    def decode(b):
        row = np.frombuffer(zlib.decompress(b[1:]), np.uint8)
        return row.reshape(8, 8, 1).astype(np.float32) / 255.0, b[0]

    m = dtpu.Model(_tiny_classifier(32))
    m.compile(optimizer=dtpu.optim.Adam(5e-3),
              loss="sparse_categorical_crossentropy",
              metrics=["accuracy"])
    with Pipeline(RecordSource(d, decode_fn=decode), None, 64, seed=0,
                  decode_workers=2) as pipe:
        hist = m.fit(pipe, epochs=8, verbose=0)
    assert hist.history["accuracy"][-1] > 0.8, hist.history
    assert m.last_fit_telemetry["input_decode_workers"] == 2
