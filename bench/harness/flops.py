"""Model operations from the configuration's shapes, and the chip's peaks.

A CNN layer's forward pass costs its weight multiply-accumulates (SAME
convolutions keep the spatial size, ``convp`` halves it after the layer,
the classifier head is included; biases, activations and pooling are not
counted). Training counts three forward passes (forward, and the two
products of the backward pass) over the samples a device really trains:
``epochs * (shard // batch) * batch``, since the ragged tail of a shard is
dropped, and nothing for padded cohort lanes or the held-out evaluation.
"""

from __future__ import annotations

import json
import os
from typing import Sequence

from bench.harness.cells import BENCH_DIR


def forward_macs(layers: Sequence, input_shape: Sequence[int],
                 num_classes: int) -> int:
    h, w, c = input_shape
    macs = 0
    for layer in layers:
        kind = layer[0]
        if kind in ("conv", "convp"):
            _, out_c, k = layer
            macs += h * w * k * k * c * out_c
            c = out_c
            if kind == "convp":
                h, w = h // 2, w // 2
        elif kind == "flatten":
            c, h, w = h * w * c, 1, 1
        elif kind == "fc":
            macs += c * layer[1]
            c = layer[1]
        else:
            raise ValueError(f"layer kind {kind!r} has no FLOP count")
    return macs + c * num_classes


def trained_samples(job: dict, shard: int) -> int:
    """Samples one device trains in one round of ``job``."""
    b = min(job["batch_size"], shard)
    return job["local_epochs"] * (shard // b) * b


def train_flops_per_device(job: dict, shard: int) -> float:
    macs = forward_macs(job["layers"], job["input_shape"], job["num_classes"])
    return 3.0 * 2.0 * macs * trained_samples(job, shard)


def shard_width(job: dict, config: dict) -> int:
    part = config["partition"]
    per_class = job["num_samples"] // job["num_classes"]
    return part["classes_per_device"] * (per_class // part["parts_per_class"])


def peaks(device_kind: str) -> dict:
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in the peak "
                       f"table ({sorted(table)})")
    return table[device_kind]
