"""Image pipeline tests (mirrors reference tests for image.py / the
ImageRecordIter path of tests/python/unittest/test_io.py)."""
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import image, recordio


def _gradient_img(h=60, w=80, seed=0):
    rs = np.random.RandomState(seed)
    yy, xx = np.mgrid[0:h, 0:w]
    img = np.stack([(yy * 3) % 256, (xx * 2) % 256,
                    ((yy + xx) * 2) % 256], -1).astype(np.uint8)
    img += rs.randint(0, 10, img.shape).astype(np.uint8)
    return img


@pytest.fixture(scope="module")
def rec_dataset(tmp_path_factory):
    """A 20-image .rec/.idx with scalar labels."""
    import cv2
    td = tmp_path_factory.mktemp("imgrec")
    path = str(td / "data.rec")
    idx = str(td / "data.idx")
    w = recordio.MXIndexedRecordIO(idx, path, "w")
    for i in range(20):
        img = _gradient_img(seed=i)
        header = recordio.IRHeader(0, float(i % 4), i, 0)
        ok, buf = cv2.imencode(".jpg", img)
        assert ok
        w.write_idx(i, recordio.pack(header, buf.tobytes()))
    w.close()
    return path, idx


def test_imdecode_imresize():
    import cv2
    img = _gradient_img()
    ok, buf = cv2.imencode(".png", img)
    out = image.imdecode(buf.tobytes(), to_rgb=1)
    assert out.shape == (60, 80, 3)
    # png is lossless; to_rgb flips channels vs cv2's BGR read
    np.testing.assert_array_equal(out, img[..., ::-1])
    small = image.imresize(out, 40, 30)
    assert small.shape == (30, 40, 3)


def test_crops():
    img = _gradient_img(100, 120)
    out, (x0, y0, w, h) = image.center_crop(img, (64, 48))
    assert out.shape == (48, 64, 3)
    assert (w, h) == (64, 48)
    out, _ = image.random_crop(img, (64, 48))
    assert out.shape == (48, 64, 3)
    out, _ = image.random_size_crop(img, (32, 32), 0.3, (0.75, 1.333))
    assert out.shape == (32, 32, 3)
    # crop bigger than source upsamples
    out, _ = image.center_crop(img, (200, 300))
    assert out.shape == (300, 200, 3)


def test_resize_short():
    img = _gradient_img(60, 80)
    out = image.resize_short(img, 30)
    assert min(out.shape[:2]) == 30
    assert out.shape[1] == 40


def test_augmenter_list():
    augs = image.CreateAugmenter((3, 32, 32), resize=40, rand_crop=True,
                                 rand_mirror=True, mean=True, std=True,
                                 brightness=0.1, contrast=0.1,
                                 saturation=0.1, pca_noise=0.1)
    img = _gradient_img()
    out = img
    for a in augs:
        out = a(out)[0]
    assert out.shape == (32, 32, 3)
    assert out.dtype == np.float32


def test_color_jitter_and_lighting():
    img = _gradient_img().astype(np.float32)
    aug = image.ColorJitterAug(0.5, 0.5, 0.5)
    out = aug(img)[0]
    assert out.shape == img.shape
    eigval = np.array([55.46, 4.794, 1.148])
    eigvec = np.random.RandomState(0).rand(3, 3)
    out = image.LightingAug(0.5, eigval, eigvec)(img)[0]
    assert out.shape == img.shape


def test_image_iter_from_rec(rec_dataset):
    path, idx = rec_dataset
    it = image.ImageIter(batch_size=4, data_shape=(3, 32, 32),
                         path_imgrec=path, path_imgidx=idx, shuffle=False)
    nbatch = 0
    labels = []
    for batch in it:
        assert batch.data[0].shape == (4, 3, 32, 32)
        assert batch.label[0].shape == (4,)
        labels.extend(batch.label[0].asnumpy().tolist())
        nbatch += 1
    assert nbatch == 5
    assert labels == [float(i % 4) for i in range(20)]


def test_image_iter_from_files(tmp_path):
    import cv2
    root = tmp_path / "raw"
    root.mkdir()
    imglist = []
    for i in range(6):
        fname = "img%d.jpg" % i
        cv2.imwrite(str(root / fname), _gradient_img(seed=i))
        imglist.append([float(i % 2), fname])
    it = image.ImageIter(batch_size=3, data_shape=(3, 24, 24),
                         imglist=imglist, path_root=str(root))
    batches = list(it)
    assert len(batches) == 2
    assert batches[0].data[0].shape == (3, 3, 24, 24)


def test_image_record_iter(rec_dataset):
    path, idx = rec_dataset
    it = image.ImageRecordIter(
        path_imgrec=path, path_imgidx=idx, data_shape=(3, 32, 32),
        batch_size=4, preprocess_threads=4, prefetch_buffer=2)
    seen = []
    for batch in it:
        assert batch.data[0].shape == (4, 3, 32, 32)
        seen.append(batch.label[0].asnumpy())
    assert len(seen) == 5
    np.testing.assert_allclose(np.concatenate(seen),
                               [float(i % 4) for i in range(20)])
    # reset + second epoch
    it.reset()
    seen2 = [b.label[0].asnumpy() for b in it]
    assert len(seen2) == 5
    it.close()


def test_image_record_iter_partition(rec_dataset):
    path, idx = rec_dataset
    it = image.ImageRecordIter(
        path_imgrec=path, path_imgidx=idx, data_shape=(3, 32, 32),
        batch_size=2, num_parts=2, part_index=1)
    n = sum(1 for _ in it)
    assert n == 5  # 10 of 20 images in this partition
    it.close()


def test_image_record_iter_trains(rec_dataset):
    """End-to-end: ImageRecordIter feeds Module.fit."""
    path, idx = rec_dataset
    it = image.ImageRecordIter(
        path_imgrec=path, path_imgidx=idx, data_shape=(3, 32, 32),
        batch_size=4, mean=True, std=True)
    data = mx.sym.Variable("data")
    net = mx.sym.Flatten(data)
    net = mx.sym.FullyConnected(net, num_hidden=4)
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(it, num_epoch=2,
            optimizer_params={"learning_rate": 0.01},
            initializer=mx.initializer.Xavier())
    it.close()


def test_record_iter_exhaustion_and_midepoch_reset(rec_dataset):
    """Pipeline-mode iterator: repeated next() after exhaustion raises
    StopIteration (no hang), and reset() mid-epoch abandons the epoch."""
    path, idx = rec_dataset
    it = mx.io.ImageRecordIter(
        path_imgrec=path, path_imgidx=idx,
        data_shape=(3, 32, 32), batch_size=8, preprocess_threads=2)
    n = sum(1 for _ in it)
    assert n == 3
    import pytest
    with pytest.raises(StopIteration):
        it.next()
    with pytest.raises(StopIteration):
        it.next()
    # mid-epoch reset
    it.reset()
    it.next()
    it.reset()
    assert sum(1 for _ in it) == 3
    it.close()


def test_image_record_uint8_iter(rec_dataset):
    """Raw-pixel iterator (reference ImageRecordUInt8Iter): uint8 batches,
    normalization rejected (belongs on device)."""
    path, idx = rec_dataset
    it = mx.io.ImageRecordUInt8Iter(
        path_imgrec=path, path_imgidx=idx, data_shape=(3, 32, 32),
        batch_size=4, preprocess_threads=2)
    b = it.next()
    arr = b.data[0].asnumpy()
    assert arr.dtype == np.uint8 or str(b.data[0].dtype) == "uint8"
    assert arr.max() > 1  # raw pixel range, not normalized
    it.close()
    import pytest
    with pytest.raises(mx.MXNetError, match="uint8"):
        mx.io.ImageRecordUInt8Iter(
            path_imgrec=path, data_shape=(3, 32, 32), batch_size=4,
            mean_r=123.0)


def _collect_epoch(path, idx, seed, threads=3):
    it = image.ImageRecordIter(
        path_imgrec=path, path_imgidx=idx, data_shape=(3, 24, 24),
        batch_size=4, preprocess_threads=threads, prefetch_buffer=2,
        rand_crop=True, rand_mirror=True, seed=seed)
    data = np.concatenate([b.data[0].asnumpy() for b in it])
    it.close()
    return data


def test_record_iter_seed_reproducible(rec_dataset):
    """Augmentation is a pure function of (seed, chunk index) — identical
    across runs and independent of worker scheduling (reference
    iter_image_recordio_2.cc seed parameter semantics)."""
    path, idx = rec_dataset
    a = _collect_epoch(path, idx, seed=11)
    b = _collect_epoch(path, idx, seed=11)
    np.testing.assert_array_equal(a, b)
    c = _collect_epoch(path, idx, seed=12)
    assert not np.array_equal(a, c)
    # explicit seed=0 is honored as a real seed (not "unset")
    d = _collect_epoch(path, idx, seed=0)
    e = _collect_epoch(path, idx, seed=0)
    np.testing.assert_array_equal(d, e)
    # the global framework seed is the default when seed is omitted
    from mxnet_tpu import random as _mxrandom
    prior = _mxrandom.get_seed()
    try:
        mx.random.seed(11)
        it = image.ImageRecordIter(
            path_imgrec=path, path_imgidx=idx, data_shape=(3, 24, 24),
            batch_size=4, preprocess_threads=3, prefetch_buffer=2,
            rand_crop=True, rand_mirror=True)
        f = np.concatenate([bb.data[0].asnumpy() for bb in it])
        it.close()
        np.testing.assert_array_equal(a, f)
    finally:
        mx.random.seed(prior)


def test_record_iter_epochs_draw_fresh_augmentation(rec_dataset):
    """Successive epochs of one iterator see different (still deterministic)
    augmentation draws — the chunk counter is monotonic across resets."""
    path, idx = rec_dataset
    it = image.ImageRecordIter(
        path_imgrec=path, path_imgidx=idx, data_shape=(3, 24, 24),
        batch_size=4, preprocess_threads=2, prefetch_buffer=2,
        rand_crop=True, rand_mirror=True, seed=5)
    e1 = np.concatenate([b.data[0].asnumpy() for b in it])
    it.reset()
    e2 = np.concatenate([b.data[0].asnumpy() for b in it])
    it.close()
    assert not np.array_equal(e1, e2)


def test_record_iter_seed_engine_fallback(rec_dataset, monkeypatch):
    """The engine-threaded fallback path honors seed too (per-image streams
    derived from the global sample ordinal)."""
    monkeypatch.setenv("MXNET_RECORDITER_PROCS", "0")
    monkeypatch.setenv("MXNET_RECORDITER_NATIVE", "0")
    path, idx = rec_dataset
    a = _collect_epoch(path, idx, seed=11)
    b = _collect_epoch(path, idx, seed=11)
    np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# native (libjpeg) pipeline — mxnet_tpu/native/imagedec.cc
# ---------------------------------------------------------------------------

def _native_available():
    from mxnet_tpu import native
    lib = native.get_lib()
    return lib is not None


needs_native = pytest.mark.skipif(not _native_available(),
                                  reason="native image pipeline unavailable")


@needs_native
def test_native_pipeline_selected_and_exact(rec_dataset, monkeypatch):
    """Supported aug sets pick the native pipeline, and its unit-scale
    center crop in exact-decode mode is byte-exact vs the cv2 decode
    reference (the default training profile uses the fast SIMD IDCT —
    see test_native_pipeline_fast_dct_tolerance)."""
    import cv2
    monkeypatch.setenv("MXNET_JPEG_DECODE_FAST", "0")
    path, idx = rec_dataset
    it = image.ImageRecordIter(
        path_imgrec=path, path_imgidx=idx, data_shape=(3, 24, 24),
        batch_size=4, preprocess_threads=2, seed=3)
    assert isinstance(it._pipeline, image._NativePipeline)
    b = it.next()
    got = b.data[0].asnumpy()  # f32 NCHW, center crop (no rand augs)
    it.close()

    r = recordio.MXIndexedRecordIO(idx, path, "r")
    for i in range(4):
        hdr, raw = recordio.unpack(r.read_idx(i))
        ref = cv2.imdecode(np.frombuffer(bytes(raw), np.uint8), 1)[..., ::-1]
        h, w = ref.shape[:2]
        y0, x0 = (h - 24) // 2, (w - 24) // 2
        ref_crop = ref[y0:y0 + 24, x0:x0 + 24].transpose(2, 0, 1)
        np.testing.assert_array_equal(got[i].astype(np.uint8), ref_crop)
    r.close()


@needs_native
def test_native_pipeline_nhwc_uint8(rec_dataset):
    path, idx = rec_dataset
    it = image.ImageRecordIter(
        path_imgrec=path, path_imgidx=idx, data_shape=(3, 24, 24),
        batch_size=4, dtype="uint8", layout="NHWC", rand_mirror=True,
        seed=3)
    b = it.next()
    arr = b.data[0].asnumpy()
    assert arr.shape == (4, 24, 24, 3) and arr.dtype == np.uint8
    assert it.provide_data[0].shape == (4, 24, 24, 3)
    it.close()


@needs_native
def test_native_pipeline_normalization(rec_dataset, monkeypatch):
    """mean/std run inside the native decoder and match numpy."""
    import cv2
    monkeypatch.setenv("MXNET_JPEG_DECODE_FAST", "0")
    path, idx = rec_dataset
    mean = [123.68, 116.28, 103.53]
    std = [58.395, 57.12, 57.375]
    it = image.ImageRecordIter(
        path_imgrec=path, path_imgidx=idx, data_shape=(3, 24, 24),
        batch_size=2, mean_r=mean[0], mean_g=mean[1], mean_b=mean[2],
        std_r=std[0], std_g=std[1], std_b=std[2], seed=3)
    assert isinstance(it._pipeline, image._NativePipeline)
    got = it.next().data[0].asnumpy()
    it.close()
    r = recordio.MXIndexedRecordIO(idx, path, "r")
    hdr, raw = recordio.unpack(r.read_idx(0))
    ref = cv2.imdecode(np.frombuffer(bytes(raw), np.uint8), 1)[..., ::-1]
    h, w = ref.shape[:2]
    y0, x0 = (h - 24) // 2, (w - 24) // 2
    crop = ref[y0:y0 + 24, x0:x0 + 24].astype(np.float32)
    refn = ((crop - np.array(mean, np.float32))
            / np.array(std, np.float32)).transpose(2, 0, 1)
    np.testing.assert_allclose(got[0], refn, atol=1e-4)
    r.close()


@needs_native
def test_native_pipeline_resize_path(rec_dataset):
    """resize (shorter-edge) before crop takes the bilinear path; output is
    close to the cv2 resize+crop reference (DCT prescale divergence only)."""
    import cv2
    path, idx = rec_dataset
    it = image.ImageRecordIter(
        path_imgrec=path, path_imgidx=idx, data_shape=(3, 24, 24),
        batch_size=2, resize=32, seed=3)
    assert isinstance(it._pipeline, image._NativePipeline)
    got = it.next().data[0].asnumpy()
    it.close()
    r = recordio.MXIndexedRecordIO(idx, path, "r")
    hdr, raw = recordio.unpack(r.read_idx(0))
    ref = cv2.imdecode(np.frombuffer(bytes(raw), np.uint8), 1)[..., ::-1]
    h, w = ref.shape[:2]
    if h > w:
        nh, nw = 32 * h // w, 32
    else:
        nh, nw = 32, 32 * w // h
    rr = cv2.resize(ref, (nw, nh), interpolation=cv2.INTER_LINEAR)
    y0, x0 = (nh - 24) // 2, (nw - 24) // 2
    refc = rr[y0:y0 + 24, x0:x0 + 24].astype(np.float32).transpose(2, 0, 1)
    err = np.abs(got[0] - refc)
    assert err.mean() < 3.0 and err.max() < 40.0
    r.close()


@needs_native
def test_native_pipeline_bad_record_skipped(tmp_path):
    """A corrupt image inside the rec stream is skipped (pad accounts for
    it), like the reference parser's per-image error tolerance."""
    import cv2
    path = str(tmp_path / "bad.rec")
    idx = str(tmp_path / "bad.idx")
    w = recordio.MXIndexedRecordIO(idx, path, "w")
    for i in range(4):
        if i == 2:
            payload = b"notajpeg" * 10
        else:
            ok, buf = cv2.imencode(".jpg", _gradient_img(seed=i))
            payload = buf.tobytes()
        w.write_idx(i, recordio.pack(
            recordio.IRHeader(0, float(i), i, 0), payload))
    w.close()
    it = image.ImageRecordIter(
        path_imgrec=path, path_imgidx=idx, data_shape=(3, 24, 24),
        batch_size=4, seed=3)
    assert isinstance(it._pipeline, image._NativePipeline)
    b = it.next()
    assert b.pad == 1  # 3 valid of 4
    labels = b.label[0].asnumpy()
    np.testing.assert_array_equal(labels[:3], [0.0, 1.0, 3.0])
    it.close()


@needs_native
def test_native_pipeline_partial_tail_batch(rec_dataset):
    """20 images, batch 8 -> last batch pad=4 with zeroed tail."""
    path, idx = rec_dataset
    it = image.ImageRecordIter(
        path_imgrec=path, path_imgidx=idx, data_shape=(3, 24, 24),
        batch_size=8, dtype="uint8", layout="NHWC", seed=3)
    batches = list(it)
    it.close()
    assert [b.pad for b in batches] == [0, 0, 4]
    tail = batches[-1].data[0].asnumpy()
    assert tail[4:].max() == 0


def test_native_pipeline_fallback_png_dataset(tmp_path):
    """A .rec of PNG payloads must not silently vanish in the native JPEG
    pipeline — the magic sniff routes it to the cv2 path."""
    import cv2
    path = str(tmp_path / "png.rec")
    idx = str(tmp_path / "png.idx")
    w = recordio.MXIndexedRecordIO(idx, path, "w")
    for i in range(6):
        ok, buf = cv2.imencode(".png", _gradient_img(seed=i))
        w.write_idx(i, recordio.pack(
            recordio.IRHeader(0, float(i), i, 0), buf.tobytes()))
    w.close()
    it = image.ImageRecordIter(
        path_imgrec=path, path_imgidx=idx, data_shape=(3, 24, 24),
        batch_size=3, seed=3)
    assert not isinstance(it._pipeline, image._NativePipeline)
    n = sum(b.data[0].shape[0] - b.pad for b in it)
    assert n == 6
    it.close()


def test_native_pipeline_fallback_unsupported_augs(rec_dataset):
    """brightness jitter isn't native — the process pipeline takes over."""
    path, idx = rec_dataset
    it = image.ImageRecordIter(
        path_imgrec=path, path_imgidx=idx, data_shape=(3, 24, 24),
        batch_size=4, brightness=0.2, seed=3)
    assert not isinstance(it._pipeline, image._NativePipeline)
    b = it.next()
    assert b.data[0].shape == (4, 3, 24, 24)
    it.close()


def test_native_pipeline_failure_surfaces(rec_dataset, monkeypatch):
    """A failure inside the native pipeline's init (a ctypes OSError, a
    missing import) breaks iterator construction: a request the native
    decoder should serve never drops to the several-fold slower cv2 path
    behind the caller's back — and the already-created uploader pool is
    still released."""
    path, idx = rec_dataset
    created = []

    def boom(self, *a, **kw):
        created.append(self._uploader)
        raise ImportError("no ml_dtypes on this host")

    monkeypatch.setattr(image._NativePipeline, "_init_native", boom)
    with pytest.raises(ImportError, match="ml_dtypes"):
        mx.io.ImageRecordIter(
            path_imgrec=path, path_imgidx=idx, data_shape=(3, 24, 24),
            batch_size=4, shuffle=False, preprocess_threads=2)
    assert created and created[0]._shutdown   # pool released on failure


@needs_native
def test_native_pipeline_fast_dct_tolerance(rec_dataset):
    """The default training decode profile (fast SIMD IDCT,
    MXNET_JPEG_DECODE_FAST=1) stays within a few 8-bit steps of the exact
    cv2 decode — augmentation noise dwarfs this, and exact mode remains
    available for byte-parity."""
    import cv2
    path, idx = rec_dataset
    it = image.ImageRecordIter(
        path_imgrec=path, path_imgidx=idx, data_shape=(3, 24, 24),
        batch_size=4, preprocess_threads=1, seed=3)
    assert isinstance(it._pipeline, image._NativePipeline)
    got = it.next().data[0].asnumpy()
    it.close()
    r = recordio.MXIndexedRecordIO(idx, path, "r")
    for i in range(4):
        hdr, raw = recordio.unpack(r.read_idx(i))
        ref = cv2.imdecode(np.frombuffer(bytes(raw), np.uint8), 1)[..., ::-1]
        h, w = ref.shape[:2]
        y0, x0 = (h - 24) // 2, (w - 24) // 2
        ref_crop = ref[y0:y0 + 24, x0:x0 + 24].transpose(2, 0, 1)
        diff = np.abs(got[i].astype(np.int32) - ref_crop.astype(np.int32))
        assert diff.max() <= 4, "fast-DCT drift too large: %d" % diff.max()
        assert diff.mean() < 1.5
        assert (diff <= 2).mean() > 0.85
    r.close()


@needs_native
def test_native_pipeline_host_batches(rec_dataset):
    """host_batches=True yields numpy-backed DataBatches with no device
    transfer (the reference's C++ parser product: CPU tensors)."""
    path, idx = rec_dataset
    it = image.ImageRecordIter(
        path_imgrec=path, path_imgidx=idx, data_shape=(3, 24, 24),
        batch_size=4, dtype="uint8", layout="NHWC", host_batches=True,
        seed=3)
    b = it.next()
    assert isinstance(b.data[0], np.ndarray)
    assert b.data[0].shape == (4, 24, 24, 3)
    assert isinstance(b.label[0], np.ndarray)
    it.close()
    # host_batches without the native pipeline is a hard error
    with pytest.raises(mx.MXNetError):
        image.ImageRecordIter(
            path_imgrec=path, path_imgidx=idx, data_shape=(3, 24, 24),
            batch_size=4, host_batches=True, brightness=0.3, seed=3)


def test_pad_crop_augmentation(rec_dataset):
    """pad=N + rand_crop (the reference CIFAR recipe, C++ augmenter
    'pad' param): borders padded before the crop, so crops can include
    fill pixels; the native pipeline declines and the cv2 path serves."""
    path, idx = rec_dataset
    it = image.ImageRecordIter(
        path_imgrec=path, path_imgidx=idx, data_shape=(3, 60, 80),
        batch_size=4, pad=6, fill_value=0, rand_crop=True, seed=3)
    assert not isinstance(it._pipeline, image._NativePipeline)
    b = it.next()
    assert b.data[0].shape == (4, 3, 60, 80)
    it.close()
    # deterministic geometry check: pad then center crop of the padded
    # size returns the padded image, whose border is the fill value
    augs = image.CreateAugmenter((3, 72, 92), pad=6, fill_value=7)
    img = _gradient_img()           # 60x80
    out = img
    for a in augs:
        out = a(out)[0]
    assert out.shape == (72, 92, 3)
    assert (out[0] == 7).all() and (out[-1] == 7).all()
    assert (out[:, 0] == 7).all() and (out[:, -1] == 7).all()


def test_pad_default_fill_is_white():
    """The ImageRecordIter parity path defaults fill_value to 255 like the
    reference C++ augmenter (image_aug_default.cc:109) — scripts passing
    pad= alone must get white padding, not black."""
    kw = image._translate_cxx_aug_params({"pad": 4})
    assert kw["fill_value"] == 255
    kw = image._translate_cxx_aug_params({"pad": 4, "fill_value": 9})
    assert kw["fill_value"] == 9


def test_host_batches_device_transform_rejected_before_pipeline(rec_dataset):
    """Incompatible host_batches+device_transform raises BEFORE any
    pipeline (reader thread / uploader pool / C++ pipe) is constructed, so
    nothing leaks on the error path."""
    import pytest

    path, idx = rec_dataset
    with pytest.raises(image.MXNetError):
        image.ImageRecordIter(
            path_imgrec=path, path_imgidx=idx, data_shape=(3, 60, 80),
            batch_size=4, host_batches=True,
            device_transform=lambda x: x)
    # no stray mxtpu pipeline threads left behind
    import threading
    assert not [t for t in threading.enumerate()
                if t.name.startswith(("mxtpu-upload", "mxtpu-rec-read"))]
