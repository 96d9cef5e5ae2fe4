"""Convergence-at-accuracy on the real chip -> CONVERGE_r05.json.

The reference's convergence tier trains cifar10 to a fixed accuracy
(tests/python/train/test_dtype.py; example train_cifar10.py recipe:
resnet-20, batch 128, sgd momentum 0.9, wd 1e-4, lr 0.05).  This harness
has no network egress, so the dataset is a deterministic synthetic
CIFAR stand-in: class templates + heavy noise + translation jitter (a
hardened variant of example/image-classification/train_cifar10.py's
synthetic_cifar — weaker signal so resnet-20 needs several epochs,
giving a convergence CURVE; the generator is local, below), packed
into RecordIO so the full production feed path runs: native libjpeg
decode -> uint8 NHWC batches -> on-device normalize folded into the
fused bf16 train step.

Round 5: runs the SAME recipe in bfloat16 AND float32 from identical
seeds and records both val-acc curves — the dtype-parity claim that
protects the bf16-default training path (reference anchor:
example/image-classification/README.md:311-315 trains across dtypes).
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "example", "image-classification"))

import numpy as np

import mxnet_tpu as mx


def pack_rec(X, y, prefix, quality=92):
    import cv2
    from mxnet_tpu import recordio
    w = recordio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "w")
    for i in range(len(X)):
        img = (X[i].transpose(1, 2, 0) * 255).astype(np.uint8)
        ok, buf = cv2.imencode(".jpg", img[..., ::-1],
                               [cv2.IMWRITE_JPEG_QUALITY, quality])
        assert ok
        hdr = recordio.IRHeader(0, float(y[i]), i, 0)
        w.write_idx(i, recordio.pack(hdr, buf.tobytes()))
    w.close()


def main():
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--num-train", type=int, default=20000)
    ap.add_argument("--num-val", type=int, default=2000)
    ap.add_argument("--batch-size", type=int, default=256)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--target-acc", type=float, default=0.90)
    ap.add_argument("--max-epochs", type=int, default=30)
    ap.add_argument("--out", type=str, default="CONVERGE_r05.json")
    ap.add_argument("--dtypes", type=str,
                    default="bfloat16,float32")
    args = ap.parse_args()

    def synthetic_cifar(num, num_classes=10, seed=0):
        """Harder variant of the example's synthetic set: weaker class
        signal + per-image geometric jitter, so resnet-20 needs several
        epochs to reach 90% — a convergence CURVE, not a one-shot fit."""
        templates = np.random.RandomState(42).rand(num_classes, 3, 32, 32)
        rs = np.random.RandomState(seed)
        labels = rs.randint(0, num_classes, size=num).astype("f")
        images = templates[labels.astype(int)] * 90
        images += rs.randn(num, 3, 32, 32) * 40
        # random roll = translation jitter (defeats pure pixel matching)
        for i in range(num):
            images[i] = np.roll(images[i],
                                (rs.randint(-2, 3), rs.randint(-2, 3)),
                                axis=(1, 2))
        return (np.clip(images, 0, 255).astype(np.float32) / 255,
                labels)

    from importlib import import_module
    net_mod = import_module("symbols.resnet")
    sym = net_mod.get_symbol(num_classes=10, num_layers=20,
                             image_shape="3,32,32")

    # cache keyed on the dataset sizes, and only valid when complete
    # v3: hardened dataset recipe (key must change when the recipe does)
    tmp = "/tmp/converge_cifar_v3_%d_%d" % (args.num_train, args.num_val)
    os.makedirs(tmp, exist_ok=True)
    t_pack = time.time()
    done_mark = os.path.join(tmp, "PACKED")
    if not os.path.exists(done_mark):
        Xtr, ytr = synthetic_cifar(args.num_train, seed=0)
        Xv, yv = synthetic_cifar(args.num_val, seed=1)
        pack_rec(Xtr, ytr, os.path.join(tmp, "train"))
        pack_rec(Xv, yv, os.path.join(tmp, "val"))
        open(done_mark, "w").write("ok")
    t_pack = time.time() - t_pack

    import jax
    import jax.numpy as jnp
    from mxnet_tpu.parallel import SPMDTrainer

    mean = jnp.array([125.3, 122.9, 113.9], jnp.float32)
    std = jnp.array([51.6, 50.8, 51.7], jnp.float32)

    def make_iter(split, train):
        return mx.io.ImageRecordIter(
            path_imgrec=os.path.join(tmp, split + ".rec"),
            path_imgidx=os.path.join(tmp, split + ".idx"),
            data_shape=(3, 32, 32), batch_size=args.batch_size,
            shuffle=train, rand_mirror=train, preprocess_threads=4,
            prefetch_buffer=4, dtype="uint8", layout="NHWC", seed=5)

    def run_dtype(dtype):
        """One full convergence run at the given compute dtype, from
        identical data, identical init seed, identical iterator seed."""
        cdt = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32

        def data_tf(x):
            x = (x.astype(jnp.float32) - mean) / std
            return jnp.transpose(x, (0, 3, 1, 2)).astype(cdt)

        tr = SPMDTrainer(sym, "sgd",
                         {"learning_rate": args.lr, "momentum": 0.9,
                          "wd": 1e-4,
                          "rescale_grad": 1.0 / args.batch_size},
                         mesh=None, compute_dtype=dtype,
                         input_transforms={"data": data_tf})
        tr.bind([("data", (args.batch_size, 3, 32, 32))],
                [("softmax_label", (args.batch_size,))])
        mx.random.seed(7)
        tr.init_params(mx.initializer.Xavier(rnd_type="gaussian",
                                             factor_type="in",
                                             magnitude=2))
        train_it = make_iter("train", True)
        val_it = make_iter("val", False)
        hist = []
        tic = time.time()
        reached = None
        for epoch in range(args.max_epochs):
            for b in train_it:
                tr.step(b.data[0], b.label[0])
            train_it.reset()
            correct = total = 0
            for b in val_it:
                # the val fetch is the epoch sync point
                outs = tr.forward_only(b.data[0], b.label[0])
                pred = np.asarray(outs[0]).argmax(-1)
                lab = np.asarray(b.label[0].asnumpy())
                k = args.batch_size - b.pad
                correct += (pred[:k] == lab[:k]).sum()
                total += k
            val_it.reset()
            acc = correct / total
            hist.append(round(float(acc), 4))
            print("[%s] epoch %d val-acc %.4f (%.1fs)"
                  % (dtype, epoch, acc, time.time() - tic))
            if acc >= args.target_acc and reached is None:
                reached = epoch + 1
                break
        wall = time.time() - tic
        train_it.close()
        val_it.close()
        tr.close()
        return {
            "compute_dtype": dtype,
            "target_val_acc": args.target_acc,
            "epochs_to_target": reached,
            "final_val_acc": hist[-1] if hist else None,
            "val_acc_per_epoch": hist,
            "wall_clock_s": round(wall, 1),
            "imgs_per_sec_end_to_end": round(
                args.num_train * len(hist) / wall, 1),
        }

    curves = {}
    for dtype in args.dtypes.split(","):
        curves[dtype] = run_dtype(dtype.strip())

    out = {
        "workload": "train_cifar10 recipe (resnet-20, sgd m=0.9 wd=1e-4, "
                    "lr=%g, batch=%d) on synthetic CIFAR stand-in "
                    "(no egress), full RecordIO->native-decode->fused-"
                    "step path on the real chip; identical seeds per "
                    "dtype" % (args.lr, args.batch_size),
        "platform": "%s (%s)" % (jax.default_backend(),
                                 jax.devices()[0].device_kind),
        "num_train": args.num_train,
        "num_val": args.num_val,
        "curves": curves,
    }
    if "bfloat16" in curves and "float32" in curves:
        b, f = curves["bfloat16"], curves["float32"]
        out["bf16_final_minus_f32_final"] = round(
            (b["final_val_acc"] or 0) - (f["final_val_acc"] or 0), 4)
        out["bf16_within_noise_of_f32"] = bool(
            abs(out["bf16_final_minus_f32_final"]) <= 0.02)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
