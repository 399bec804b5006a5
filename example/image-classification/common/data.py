"""Data loaders for the image-classification examples.

Port of reference example/image-classification/common/data.py: rec-file
iterators with the standard augmentation set, plus the synthetic
benchmark path.
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", ".."))

import mxnet_tpu as mx
from .fit import SyntheticDataIter


def add_data_args(parser):
    data = parser.add_argument_group("Data")
    data.add_argument("--data-train", type=str, default=None,
                      help="training .rec file")
    data.add_argument("--data-train-idx", type=str, default="")
    data.add_argument("--data-val", type=str, default=None)
    data.add_argument("--data-val-idx", type=str, default="")
    data.add_argument("--image-shape", type=str, default="3,224,224")
    data.add_argument("--num-classes", type=int, default=1000)
    data.add_argument("--rgb-mean", type=str, default="123.68,116.779,103.939")
    data.add_argument("--rgb-std", type=str, default="1,1,1")
    data.add_argument("--pad-size", type=int, default=0)
    data.add_argument("--data-nthreads", type=int, default=4)
    return data


def add_data_aug_args(parser):
    aug = parser.add_argument_group("Augmentation")
    aug.add_argument("--random-crop", type=int, default=1)
    aug.add_argument("--random-mirror", type=int, default=1)
    aug.add_argument("--max-random-scale", type=float, default=1.0)
    aug.add_argument("--min-random-scale", type=float, default=1.0)
    aug.add_argument("--brightness", type=float, default=0.0)
    aug.add_argument("--contrast", type=float, default=0.0)
    aug.add_argument("--saturation", type=float, default=0.0)
    aug.add_argument("--pca-noise", type=float, default=0.0)
    aug.add_argument("--random-h", type=int, default=0)
    aug.add_argument("--random-s", type=int, default=0)
    aug.add_argument("--random-l", type=int, default=0)
    return aug


def get_rec_iter(args, kv=None):
    """(reference common/data.py get_rec_iter) — falls back to synthetic
    batches when --benchmark 1 or no --data-train is given."""
    image_shape = tuple(int(x) for x in args.image_shape.split(","))
    chlast = getattr(args, "layout", "NCHW") == "NHWC"
    if getattr(args, "benchmark", 0) or not args.data_train:
        c, h, w = image_shape
        data_shape = (args.batch_size, h, w, c) if chlast \
            else (args.batch_size,) + image_shape
        train = SyntheticDataIter(args.num_classes, data_shape,
                                  max_iter=max(args.num_examples
                                               // args.batch_size, 1),
                                  dtype=args.dtype)
        return train, None
    rank, nworker = (kv.rank, kv.num_workers) if kv else (0, 1)
    mean = [float(x) for x in args.rgb_mean.split(",")]
    std = [float(x) for x in args.rgb_std.split(",")]
    train = mx.io.ImageRecordIter(
        path_imgrec=args.data_train,
        path_imgidx=args.data_train_idx or None,
        data_shape=image_shape,
        batch_size=args.batch_size,
        shuffle=True,
        rand_crop=bool(args.random_crop),
        rand_mirror=bool(args.random_mirror),
        max_random_scale=args.max_random_scale,
        min_random_scale=args.min_random_scale,
        brightness=args.brightness,
        contrast=args.contrast,
        saturation=args.saturation,
        pca_noise=args.pca_noise,
        random_h=args.random_h,
        random_s=args.random_s,
        random_l=args.random_l,
        mean_r=mean[0], mean_g=mean[1], mean_b=mean[2],
        std_r=std[0], std_g=std[1], std_b=std[2],
        preprocess_threads=args.data_nthreads,
        num_parts=nworker, part_index=rank,
        dtype=args.dtype)
    val = None
    if args.data_val:
        val = mx.io.ImageRecordIter(
            path_imgrec=args.data_val,
            path_imgidx=args.data_val_idx or None,
            data_shape=image_shape,
            batch_size=args.batch_size,
            rand_crop=False, rand_mirror=False,
            mean_r=mean[0], mean_g=mean[1], mean_b=mean[2],
            std_r=std[0], std_g=std[1], std_b=std[2],
            preprocess_threads=args.data_nthreads,
            num_parts=nworker, part_index=rank,
            dtype=args.dtype)
    if chlast:
        train = ChannelLastIter(train)
        if val is not None:
            val = ChannelLastIter(val)
    return train, val


class ChannelLastIter:
    """Wrap an NCHW iterator to yield NHWC batches — the TPU-preferred
    layout. The decode pipeline stays NCHW per the
    reference iterator contract; the relayout happens host-side here."""

    def __init__(self, inner):
        self._inner = inner
        self.batch_size = inner.batch_size
        d = inner.provide_data[0]
        n, c, h, w = d.shape
        self.provide_data = [mx.io.DataDesc(d.name, (n, h, w, c), d.dtype,
                                            layout="NHWC")]
        self.provide_label = inner.provide_label

    def reset(self):
        self._inner.reset()

    def __iter__(self):
        return self

    def next(self):
        b = self._inner.next()
        data = [mx.nd.transpose(x, axes=(0, 2, 3, 1)) for x in b.data]
        return mx.io.DataBatch(data=data, label=b.label, pad=b.pad,
                               provide_data=self.provide_data,
                               provide_label=self.provide_label)

    __next__ = next
