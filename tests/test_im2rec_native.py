"""Native im2rec packer (native/im2rec.cc) vs the Python pool.

Reference parity: tools/im2rec.cc (the C++ multithreaded packer).
Both paths must produce a RecordIO set with the same ids, labels and
record count, readable by MXIndexedRecordIO and ImageRecordIter, with
per-image decode output close to the cv2-packed one (different JPEG
encoders — libjpeg here, cv2's libjpeg there — may differ by a few
8-bit steps after one re-encode cycle).
"""
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.abspath(os.path.join(HERE, ".."))

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import native, recordio  # noqa: E402


def _native_available():
    lib = native.get_lib()
    return lib is not None


@pytest.fixture(scope="module")
def image_root(tmp_path_factory):
    import cv2
    root = tmp_path_factory.mktemp("imgs")
    rs = np.random.RandomState(0)
    for c in range(2):
        d = root / ("cls%d" % c)
        d.mkdir()
        for i in range(8):
            img = np.clip(
                cv2.GaussianBlur(rs.rand(80, 100, 3) * 255, (9, 9), 3)
                + rs.randn(80, 100, 3) * 10, 0, 255).astype(np.uint8)
            cv2.imwrite(str(d / ("%d.jpg" % i)), img)
    return str(root)


def _pack(image_root, prefix, native_flag):
    subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "im2rec.py"),
         prefix, image_root, "--list", "--recursive"], check=True)
    subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "im2rec.py"),
         prefix, image_root, "--resize", "64", "--num-thread", "2",
         "--native", "1" if native_flag else "0"],
        check=True)


@pytest.mark.skipif(not _native_available(), reason="no native im2rec")
def test_native_matches_python_pack(image_root, tmp_path):
    import cv2
    np_prefix = str(tmp_path / "pypack")
    nat_prefix = str(tmp_path / "natpack")
    _pack(image_root, np_prefix, native_flag=False)
    _pack(image_root, nat_prefix, native_flag=True)

    def read_all(prefix):
        rec = recordio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec",
                                         "r")
        out = {}
        for k in rec.keys:
            hdr, img = recordio.unpack_img(rec.read_idx(k))
            out[k] = (hdr.label, img)
        rec.close()
        return out

    py = read_all(np_prefix)
    nat = read_all(nat_prefix)
    assert set(py) == set(nat) and len(py) == 16
    for k in py:
        lab_p, img_p = py[k]
        lab_n, img_n = nat[k]
        assert float(lab_p) == float(lab_n)
        assert img_p.shape == img_n.shape
        assert img_p.shape[0] == 64 or img_p.shape[1] == 64  # short edge
        # decoded content close despite different JPEG encoders
        diff = np.abs(img_p.astype(int) - img_n.astype(int)).mean()
        assert diff < 8.0, diff

    # the native .rec feeds the training iterator
    it = mx.io.ImageRecordIter(
        path_imgrec=nat_prefix + ".rec", path_imgidx=nat_prefix + ".idx",
        data_shape=(3, 56, 56), batch_size=4, shuffle=True,
        preprocess_threads=2, seed=0)
    n = sum(b.data[0].shape[0] - b.pad for b in it)
    assert n == 16
    it.close()


@pytest.mark.skipif(not _native_available(), reason="no native im2rec")
def test_native_pass_through_is_byte_exact(image_root, tmp_path):
    prefix = str(tmp_path / "pt")
    subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "im2rec.py"),
         prefix, image_root, "--list", "--recursive"], check=True)
    subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "im2rec.py"),
         prefix, image_root, "--pass-through", "--native", "1"],
        check=True)
    rec = recordio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "r")
    # every payload is the source file byte-for-byte
    with open(prefix + ".lst") as f:
        rows = [ln.strip().split("\t") for ln in f if ln.strip()]
    for row in rows:
        idx, path = int(row[0]), row[-1]
        hdr, payload = recordio.unpack(rec.read_idx(idx))
        with open(os.path.join(image_root, path), "rb") as f:
            assert payload == f.read()
        assert hdr.id == idx
    rec.close()
