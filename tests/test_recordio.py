"""Native RecordIO + ImageRecordIter tests, modeled on the reference's
tests/python/unittest/test_recordio.py and the ImageRecordIter cases of
test_io.py."""
import os
import struct

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import recordio


# ---------------------------------------------------------------------------
# raw record container
# ---------------------------------------------------------------------------
def test_recordio_roundtrip(tmp_path):
    path = str(tmp_path / "a.rec")
    w = recordio.MXRecordIO(path, "w")
    payloads = [b"hello", b"", b"x" * 1000, b"odd123"]
    for p in payloads:
        w.write(p)
    w.close()

    r = recordio.MXRecordIO(path, "r")
    got = []
    while True:
        rec = r.read()
        if rec is None:
            break
        got.append(rec)
    r.close()
    assert got == payloads


def test_recordio_magic_escape(tmp_path):
    """Payloads containing the wire magic must round-trip (dmlc recordio
    split/reassemble protocol)."""
    magic = struct.pack("<I", 0xced7230a)
    payloads = [magic, b"ab" + magic + b"cd", magic * 3, b"z" * 7 + magic]
    path = str(tmp_path / "m.rec")
    w = recordio.MXRecordIO(path, "w")
    for p in payloads:
        w.write(p)
    w.close()
    r = recordio.MXRecordIO(path, "r")
    for expect in payloads:
        assert r.read() == expect
    assert r.read() is None
    r.close()


def test_indexed_recordio(tmp_path):
    rec, idx = str(tmp_path / "b.rec"), str(tmp_path / "b.idx")
    w = recordio.MXIndexedRecordIO(idx, rec, "w")
    for i in range(20):
        w.write_idx(i, b"record-%d" % i)
    w.close()

    r = recordio.MXIndexedRecordIO(idx, rec, "r")
    assert r.keys == list(range(20))
    # random access, out of order
    for i in [7, 0, 19, 3, 3]:
        assert r.read_idx(i) == b"record-%d" % i
    r.close()


def test_irheader_pack_unpack():
    h = recordio.IRHeader(0, 3.0, 42, 0)
    s = recordio.pack(h, b"payload")
    h2, payload = recordio.unpack(s)
    assert payload == b"payload"
    assert h2.label == 3.0 and h2.id == 42
    # multi-label
    hm = recordio.IRHeader(0, np.array([1.0, 2.0, 3.0], np.float32), 7, 0)
    s = recordio.pack(hm, b"img")
    h3, payload = recordio.unpack(s)
    assert payload == b"img"
    np.testing.assert_array_equal(h3.label, [1.0, 2.0, 3.0])
    assert h3.flag == 3


def test_pack_img_roundtrip():
    cv2 = pytest.importorskip("cv2")
    yy, xx = np.mgrid[0:32, 0:24]
    img = np.stack([yy * 8, xx * 10, (yy + xx) * 4], axis=-1).astype(np.uint8)
    s = recordio.pack_img(recordio.IRHeader(0, 1.0, 0, 0), img, quality=95)
    header, decoded = recordio.unpack_img(s)
    assert header.label == 1.0
    assert decoded.shape == (32, 24, 3)
    # JPEG is lossy; mean error should still be small
    assert np.abs(decoded.astype(int) - img.astype(int)).mean() < 12


# ---------------------------------------------------------------------------
# the native image pipeline
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def image_rec(tmp_path_factory):
    """A tiny 3-class jpeg dataset packed with im2rec's code path."""
    cv2 = pytest.importorskip("cv2")
    root = tmp_path_factory.mktemp("imgs")
    prefix = str(root / "data")
    n_per_class, size = 8, 40
    rng = np.random.RandomState(1)
    rec = recordio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "w")
    idx = 0
    labels = {}
    for cls in range(3):
        base = np.full((size, size, 3), cls * 80 + 40, np.uint8)
        for _ in range(n_per_class):
            img = (base + rng.randint(0, 20, base.shape)).astype(np.uint8)
            rec.write_idx(idx, recordio.pack_img(
                recordio.IRHeader(0, float(cls), idx, 0), img))
            labels[idx] = cls
            idx += 1
    rec.close()
    return prefix, labels


def test_image_record_iter(image_rec):
    prefix, labels = image_rec
    it = mx.io.ImageRecordIter(
        path_imgrec=prefix + ".rec", data_shape=(3, 32, 32), batch_size=6,
        shuffle=False, preprocess_threads=2, round_batch=False)
    assert it.num_records == 24
    batches = list(it)
    assert len(batches) == 4  # 24 / 6
    b = batches[0]
    assert b.data[0].shape == (6, 3, 32, 32)
    assert b.label[0].shape == (6,)
    # unshuffled: first six labels are class 0
    np.testing.assert_array_equal(b.label[0].asnumpy(), [0] * 6)
    # pixel content: class-0 images have mean ~40-60 before normalize
    mean_px = float(b.data[0].asnumpy().mean())
    assert 30 < mean_px < 70

    # reset replays the epoch
    it.reset()
    again = next(it)
    np.testing.assert_allclose(again.data[0].asnumpy(),
                               b.data[0].asnumpy(), rtol=1e-6)


def test_image_record_iter_with_idx(image_rec):
    """path_imgidx loads offsets from the .idx sidecar (no full .rec scan)
    and yields the identical stream."""
    prefix, labels = image_rec
    it = mx.io.ImageRecordIter(
        path_imgrec=prefix + ".rec", path_imgidx=prefix + ".idx",
        data_shape=(3, 32, 32), batch_size=6,
        shuffle=False, preprocess_threads=2, round_batch=False)
    assert it.num_records == 24
    ref = mx.io.ImageRecordIter(
        path_imgrec=prefix + ".rec", data_shape=(3, 32, 32), batch_size=6,
        shuffle=False, preprocess_threads=2, round_batch=False)
    for b, r in zip(it, ref):
        np.testing.assert_allclose(b.data[0].asnumpy(), r.data[0].asnumpy(),
                                   rtol=1e-6)
        np.testing.assert_array_equal(b.label[0].asnumpy(), r.label[0].asnumpy())


def test_image_record_iter_grayscale(image_rec):
    """c=1 data_shape converts color JPEGs via BT.601 luma, not channel R."""
    prefix, labels = image_rec
    it = mx.io.ImageRecordIter(
        path_imgrec=prefix + ".rec", data_shape=(1, 32, 32), batch_size=6,
        shuffle=False, preprocess_threads=2, round_batch=False)
    b = next(it)
    assert b.data[0].shape == (6, 1, 32, 32)
    # class-0 grey-ish images: luma ≈ channel mean ≈ 40-60
    mean_px = float(b.data[0].asnumpy().mean())
    assert 30 < mean_px < 70


def test_image_record_iter_shuffle_and_augment(image_rec):
    prefix, labels = image_rec
    it = mx.io.ImageRecordIter(
        path_imgrec=prefix + ".rec", data_shape=(3, 32, 32), batch_size=8,
        shuffle=True, rand_mirror=True, rand_crop=True, resize=36,
        mean_r=128.0, mean_g=128.0, mean_b=128.0,
        std_r=64.0, std_g=64.0, std_b=64.0,
        preprocess_threads=3, seed=5)
    seen = []
    for batch in it:
        seen.extend(batch.label[0].asnumpy().astype(int).tolist())
    assert len(seen) == 24
    # shuffled order interleaves classes
    assert seen[:8] != [0] * 8
    # all records seen exactly once per epoch
    assert sorted(seen) == sorted(labels.values())

    # normalization applied: class means map near (value-128)/64
    it.reset()
    batch = next(it)
    data = batch.data[0].asnumpy()
    assert -3.0 < data.mean() < 3.0


def test_image_record_iter_round_batch(image_rec):
    prefix, _ = image_rec
    it = mx.io.ImageRecordIter(
        path_imgrec=prefix + ".rec", data_shape=(3, 32, 32), batch_size=10,
        shuffle=False, round_batch=True, preprocess_threads=2)
    batches = list(it)
    # 24 records, batch 10 → 3 batches with wrap-around padding
    assert len(batches) == 3


def test_image_record_iter_provide(image_rec):
    prefix, _ = image_rec
    it = mx.io.ImageRecordIter(
        path_imgrec=prefix + ".rec", data_shape=(3, 32, 32), batch_size=4)
    d = it.provide_data[0]
    assert d.shape == (4, 3, 32, 32)
    assert it.provide_label[0].shape == (4,)


def test_pack_numpy_scalar_label():
    """np.float32 labels must take the scalar wire path (flag=0)."""
    s = recordio.pack(recordio.IRHeader(0, np.float32(3.0), 5, 0), b"p")
    h, payload = recordio.unpack(s)
    assert h.flag == 0 and float(h.label) == 3.0 and payload == b"p"


def test_pickle_reader_refuse_open_writer(tmp_path):
    import pickle

    path = str(tmp_path / "p.rec")
    w = recordio.MXRecordIO(path, "w")
    w.write(b"keep-me")
    with pytest.raises(Exception):
        pickle.dumps(w)  # open writer must refuse (would truncate)
    w.close()
    r = recordio.MXRecordIO(path, "r")
    r2 = pickle.loads(pickle.dumps(r))
    assert r2.read() == b"keep-me"
    # the original file was never truncated
    assert recordio.MXRecordIO(path, "r").read() == b"keep-me"


def test_image_iter_partial_tail_pad(image_rec):
    """24 records, batch 10: the tail batch is emitted with pad reported."""
    prefix, labels = image_rec
    for round_batch in (True, False):
        it = mx.io.ImageRecordIter(
            path_imgrec=prefix + ".rec", data_shape=(3, 32, 32),
            batch_size=10, shuffle=False, round_batch=round_batch,
            preprocess_threads=2)
        batches = list(it)
        assert len(batches) == 3
        assert [b.pad for b in batches] == [0, 0, 6]
        seen = []
        for b in batches[:-1]:
            seen.extend(b.label[0].asnumpy().astype(int).tolist())
        last = batches[-1].label[0].asnumpy().astype(int).tolist()
        seen.extend(last[:4])  # ignore pad
        assert sorted(seen) == sorted(labels.values())


def test_image_iter_small_dataset_pads(tmp_path):
    """Datasets smaller than one batch still yield a (padded) batch."""
    cv2 = pytest.importorskip("cv2")
    prefix = str(tmp_path / "small")
    rec = recordio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "w")
    for i in range(3):
        img = np.full((16, 16, 3), 50 * (i + 1), np.uint8)
        rec.write_idx(i, recordio.pack_img(
            recordio.IRHeader(0, float(i), i, 0), img))
    rec.close()
    it = mx.io.ImageRecordIter(path_imgrec=prefix + ".rec",
                               data_shape=(3, 16, 16), batch_size=8,
                               shuffle=False, preprocess_threads=1)
    batches = list(it)
    assert len(batches) == 1
    assert batches[0].pad == 5
    np.testing.assert_array_equal(
        batches[0].label[0].asnumpy()[:3].astype(int), [0, 1, 2])


def test_image_iter_grayscale_raw(tmp_path):
    """c=1 raw payloads read with single-channel stride (no OOB)."""
    prefix = str(tmp_path / "gray")
    rec = recordio.MXRecordIO(prefix + ".rec", "w")
    for i in range(4):
        raw = np.full((6, 6, 1), 10 * (i + 1), np.uint8)
        rec.write(recordio.pack(recordio.IRHeader(0, float(i), i, 0),
                                raw.tobytes()))
    rec.close()
    it = mx.io.ImageRecordIter(path_imgrec=prefix + ".rec",
                               data_shape=(1, 6, 6), batch_size=4,
                               shuffle=False, preprocess_threads=1)
    b = next(it)
    data = b.data[0].asnumpy()
    assert data.shape == (4, 1, 6, 6)
    for i in range(4):
        np.testing.assert_array_equal(data[i], np.full((1, 6, 6),
                                                       10.0 * (i + 1)))


def test_native_library_staleness_is_a_source_hash(tmp_path, monkeypatch):
    """mtimes say nothing after a copy or a checkout: the loader rebuilds
    when the hash recorded beside the library differs from the
    sources'."""
    from mxnet_tpu import _native

    _native.lib()  # built (or found current) for this checkout
    assert not _native._needs_build()
    with open(_native._STAMP) as f:
        assert f.read().strip() == _native._source_hash()
    stale = tmp_path / "stamp"
    stale.write_text("0" * 64 + "\n")
    monkeypatch.setattr(_native, "_STAMP", str(stale))
    assert _native._needs_build()
    monkeypatch.setattr(_native, "_STAMP", str(tmp_path / "missing"))
    assert _native._needs_build()
