"""Seeded inputs and weights for the benchmark: one general generator per
feed kind, driven only by the parameters of a traffic file
(``benchmark/traffic/<traffic>.json``) and ``--seed``.

``recordio``: a RecordIO file of photographic-looking JPEGs (copied from
``bench._make_dataset``: smooth gradients + low-frequency texture, ~13 KB
an image at q90 — white noise would decode several times slower than any
photo), textures and label order drawn from the seed.  ``tokens``: int32
token rows with the next token as label.  Every seed gives the same
sizes, so the seed changes the values and never the amount of work.
"""
import os

import numpy as np

SEED_MASK = 0x7FFFFFFF


def np_rng(seed, stream=0):
    """A numpy generator from any whole-number seed (the driver's are over
    2**31) and a stream number that keeps data, labels and weights apart."""
    return np.random.RandomState([int(seed) & SEED_MASK, int(seed) >> 31,
                                  int(stream)])


def jax_key(seed, stream=0):
    import jax
    key = jax.random.PRNGKey(int(seed) & SEED_MASK)
    return jax.random.fold_in(jax.random.fold_in(key, int(seed) >> 31),
                              int(stream))


def exact_labels(classes, count=None, dtype_bits=8):
    """``count`` (default: all) class ids below ``classes`` that a float with
    ``dtype_bits`` significand bits holds exactly: all ids up to
    2**dtype_bits, then every 2nd up to 2**(bits+1), every 4th above."""
    ids, step, lo = [], 1, 0
    hi = 2 ** dtype_bits
    while lo < classes:
        ids.extend(range(lo, min(hi, classes), step))
        lo, hi, step = hi, hi * 2, step * 2
    ids = np.asarray(ids, np.int64)
    assert count is None or len(ids) >= count, (len(ids), count)
    return ids[:count]


def make_recordio(directory, seed, images, side, classes, label_ids,
                  epoch_images=None, textures=16, quality=90):
    """Write ``images`` JPEGs of ``side`` x ``side`` into
    ``<directory>/train.rec|.idx``; labels are drawn from the seed out of
    the first ``label_ids`` exactly representable ids below ``classes``.
    The index lists ``epoch_images`` keys (default ``images``), key k
    naming record k % images: an epoch as long as a real one — whose end,
    a reshuffle and a restart of the decoders, a trainer meets every few
    thousand steps, not every eighth — over a file a run can afford to
    write.  Returns the prefix."""
    import cv2

    from mxnet_tpu import recordio

    rs = np_rng(seed, 1)
    prefix = os.path.join(directory, "train")
    xs = np.linspace(0, 1, side)
    bank = [cv2.GaussianBlur(rs.randn(side, side, 3).astype(np.float32) * 40,
                             (7, 7), 0) for _ in range(textures)]
    ids = exact_labels(classes, label_ids)
    labels = ids[rs.randint(0, len(ids), images)]
    tints = rs.uniform(100, 255, (images, 3))
    rec = recordio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "w")
    for i in range(images):
        base = (np.outer(xs, np.roll(xs, (i * 37) % side))[..., None]
                * tints[i]).astype(np.float32)
        img = np.clip(base + bank[i % textures], 0, 255).astype(np.uint8)
        header = recordio.IRHeader(0, float(labels[i]), i, 0)
        rec.write_idx(i, recordio.pack_img(header, img, quality=quality))
    rec.close()
    if epoch_images and epoch_images > images:
        with open(prefix + ".idx") as f:
            offsets = [line.split("\t")[1].strip() for line in f]
        with open(prefix + ".idx", "w") as f:
            for k in range(int(epoch_images)):
                f.write("%d\t%s\n" % (k, offsets[k % images]))
    return prefix


def make_tokens(seed, rows, seq_len, vocab, exact_bits=8):
    """(data, label) int32 arrays of shape (rows, seq_len): label is the
    next token of the same seeded row.  Ids are drawn, over the whole
    vocabulary's range, from those a float of ``exact_bits`` significand
    bits holds exactly (NDArrayIter hands floats on and the trainer casts
    them to its compute dtype)."""
    ids = exact_labels(vocab, None, exact_bits)
    toks = ids[np_rng(seed, 2).randint(0, len(ids), (rows, seq_len + 1))]
    toks = toks.astype(np.int32)
    return toks[:, :-1].copy(), toks[:, 1:].copy()
