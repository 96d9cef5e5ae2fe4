#!/usr/bin/env python
"""im2rec: pack an image dataset into RecordIO (reference tools/im2rec.py).

Two modes, CLI-compatible with the reference:

* --list: walk an image root, assign integer labels per subdirectory, and
  write ``prefix.lst`` ("index\\tlabel\\trelpath" lines, optional
  train/val/test split via --train-ratio/--test-ratio).
* pack (default): read ``prefix.lst``, encode each image (optional
  --resize shorter-side resize, --quality, --center-crop) and write
  ``prefix.rec`` + ``prefix.idx`` with pack_img, using --num-thread worker
  threads feeding a single writer.
"""
from __future__ import annotations

import argparse
import os
import random
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def list_image(root, recursive, exts):
    """Yield (index, relpath, label) walking root."""
    i = 0
    if recursive:
        cat = {}
        for path, dirs, files in sorted(os.walk(root, followlinks=True)):
            dirs.sort()
            files.sort()
            for fname in files:
                fpath = os.path.join(path, fname)
                suffix = os.path.splitext(fname)[1].lower()
                if os.path.isfile(fpath) and (suffix in exts):
                    if path not in cat:
                        cat[path] = len(cat)
                    yield (i, os.path.relpath(fpath, root), cat[path])
                    i += 1
    else:
        for fname in sorted(os.listdir(root)):
            fpath = os.path.join(root, fname)
            suffix = os.path.splitext(fname)[1].lower()
            if os.path.isfile(fpath) and (suffix in exts):
                yield (i, os.path.relpath(fpath, root), 0)
                i += 1


def write_list(path_out, image_list):
    with open(path_out, "w") as fout:
        for i, item in enumerate(image_list):
            line = "%d\t" % item[0]
            for j in item[2:]:
                line += "%f\t" % j
            line += "%s\n" % item[1]
            fout.write(line)


def make_list(args):
    image_list = list(list_image(args.root, args.recursive, args.exts))
    if args.shuffle:
        random.seed(100)
        random.shuffle(image_list)
    n = len(image_list)
    sep = int(n * args.train_ratio)
    sep_test = int(n * args.test_ratio)
    if args.train_ratio == 1.0:
        write_list(args.prefix + ".lst", image_list)
    else:
        if args.test_ratio:
            write_list(args.prefix + "_test.lst", image_list[:sep_test])
        if args.train_ratio + args.test_ratio < 1.0:
            write_list(args.prefix + "_val.lst", image_list[sep_test + sep:])
        write_list(args.prefix + "_train.lst",
                   image_list[sep_test:sep_test + sep])


def read_list(path_in):
    with open(path_in) as fin:
        for line in fin:
            line = [i.strip() for i in line.strip().split("\t")]
            if len(line) < 3:
                continue
            yield (int(line[0]), line[-1]) + tuple(map(float, line[1:-1]))


def image_encode(args, item, path):
    """Read + transform + encode one image; returns packed record bytes."""
    from mxnet_tpu import recordio

    header = recordio.IRHeader(
        0, item[2] if len(item) == 3 else np.array(item[2:], "f"),
        item[0], 0)
    if args.pass_through:  # raw bytes: no decoder needed
        with open(path, "rb") as fin:
            return recordio.pack(header, fin.read())
    import cv2
    img = cv2.imread(path, args.color)
    if img is None:
        raise IOError("cannot read %s" % path)
    if args.center_crop and img.shape[0] != img.shape[1]:
        margin = (max(img.shape[:2]) - min(img.shape[:2])) // 2
        if img.shape[0] > img.shape[1]:
            img = img[margin:margin + img.shape[1], :]
        else:
            img = img[:, margin:margin + img.shape[0]]
    if args.resize:
        h, w = img.shape[:2]
        if h > w:
            new_w, new_h = args.resize, int(h * args.resize / w)
        else:
            new_w, new_h = int(w * args.resize / h), args.resize
        img = cv2.resize(img, (new_w, new_h))
    return recordio.pack_img(header, img, quality=args.quality,
                             img_fmt=args.encoding)


def make_record_native(args):
    """Pack via the C++ packer (native/im2rec.cc — the reference
    tools/im2rec.cc analog): libjpeg decode -> shorter-edge resize ->
    libjpeg encode on a worker pool, list-ordered records.  Returns
    False when the native library is unavailable or the requested
    options aren't covered (the Python path then serves)."""
    from mxnet_tpu import native as _native
    lib = _native.get_lib()
    if lib is None:
        return False
    if args.center_crop or args.encoding != ".jpg" or args.color != 1:
        return False   # cv2-only options
    # the native packer covers single-label JPEG lists; multi-label rows
    # (label arrays) and non-JPEG sources keep the Python path, which
    # transcodes/encodes them correctly
    with open(args.prefix + ".lst") as f:
        for line in f:
            fields = line.rstrip("\n").split("\t")
            if len(fields) < 3:
                continue
            if len(fields) > 3:
                return False   # multi-label
            if not args.pass_through and \
                    not fields[-1].lower().endswith((".jpg", ".jpeg")):
                return False   # non-JPEG needs cv2 transcoding
    import ctypes
    packed = ctypes.c_uint64(0)
    skipped = ctypes.c_uint64(0)
    tic = time.time()
    rc = lib.MXTPUIm2Rec(
        (args.prefix + ".lst").encode(), args.root.encode(),
        (args.prefix + ".rec").encode(), (args.prefix + ".idx").encode(),
        0 if args.pass_through else args.resize, args.quality,
        max(1, args.num_thread), 1 if args.pass_through else 0,
        ctypes.byref(packed), ctypes.byref(skipped))
    if rc != 0:
        raise RuntimeError("native im2rec failed rc=%d" % rc)
    print("packed %d records into %s.rec (%d skipped) [native, %.1fs]"
          % (packed.value, args.prefix, skipped.value, time.time() - tic))
    return True


def make_record(args):
    """Pack prefix.lst -> prefix.rec/.idx with a decode worker pool ordered
    through the host dependency engine."""
    import threading

    from mxnet_tpu import engine as eng
    from mxnet_tpu import recordio

    items = list(read_list(args.prefix + ".lst"))
    record = recordio.MXIndexedRecordIO(
        args.prefix + ".idx", args.prefix + ".rec", "w")
    engine = eng.Engine(num_workers=max(1, args.num_thread))
    results = {}
    write_var = engine.new_variable()
    count = [0]
    skipped = [0]
    tic = time.time()
    # Bound decoded-but-unwritten records held in memory.
    inflight = threading.Semaphore(4 * max(1, args.num_thread))

    def encode_one(i, item):
        path = os.path.join(args.root, item[1])
        try:
            results[i] = image_encode(args, item, path)
        except Exception as e:  # skip unreadable images, as the reference does
            print("skipping %s: %s" % (path, e))
            results[i] = None

    def write_one(i, item):
        buf = results.pop(i)
        inflight.release()
        if buf is None:
            skipped[0] += 1
            return
        record.write_idx(item[0], buf)
        count[0] += 1
        if count[0] % 1000 == 0:
            print("time: %.3f count: %d" % (time.time() - tic, count[0]))

    for i, item in enumerate(items):
        inflight.acquire()
        enc_var = engine.new_variable()
        engine.push(lambda i=i, item=item: encode_one(i, item),
                    mutable_vars=(enc_var,), name="imdecode")
        # Writes serialize on write_var in push order -> .rec order == .lst
        # order even though decodes run in parallel.
        engine.push(lambda i=i, item=item: write_one(i, item),
                    const_vars=(enc_var,), mutable_vars=(write_var,),
                    name="record_write")
        # Dependency-ordered: reclaimed after its consumers complete.
        engine.delete_variable(enc_var)
    engine.wait_for_all()
    engine.shutdown()
    record.close()
    print("packed %d records into %s.rec (%d skipped)"
          % (count[0], args.prefix, skipped[0]))


def parse_args():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("prefix", help="prefix of .lst/.rec/.idx files")
    p.add_argument("root", help="image root folder")
    p.add_argument("--list", action="store_true",
                   help="create image list instead of packing")
    p.add_argument("--exts", nargs="+",
                   default=[".jpeg", ".jpg", ".png"])
    p.add_argument("--recursive", action="store_true")
    p.add_argument("--shuffle",
                   type=lambda s: s.strip().lower() in
                   ("1", "true", "yes", "on"),
                   default=True)
    p.add_argument("--train-ratio", type=float, default=1.0)
    p.add_argument("--test-ratio", type=float, default=0.0)
    p.add_argument("--resize", type=int, default=0)
    p.add_argument("--center-crop", action="store_true")
    p.add_argument("--quality", type=int, default=95)
    p.add_argument("--encoding", choices=[".jpg", ".png"], default=".jpg")
    p.add_argument("--pass-through", action="store_true",
                   help="skip transcoding, pack raw bytes")
    p.add_argument("--color", type=int, default=1, choices=[-1, 0, 1])
    p.add_argument("--num-thread", type=int, default=1)
    p.add_argument("--native", type=lambda s: s.strip().lower() in
                   ("1", "true", "yes", "on"), default=True,
                   help="use the C++ packer when available (falls back "
                        "to the Python pool otherwise)")
    return p.parse_args()


if __name__ == "__main__":
    args = parse_args()
    if args.list:
        make_list(args)
    elif not (args.native and make_record_native(args)):
        make_record(args)
