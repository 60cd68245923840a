"""High-level hapi training (reference: paddle.Model.fit).

    python examples/train_vision_hapi.py

Demonstrates: hapi Model.fit with callbacks, metrics, and the compiled
train step underneath (one XLA program per step).
"""
import numpy as np

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import paddle_tpu as paddle
from paddle_tpu import optimizer
from paddle_tpu.hapi import Model
from paddle_tpu.metric import Accuracy
from paddle_tpu.vision.models import LeNet


def main():
    paddle.seed(0)
    net = LeNet(num_classes=10)
    model = Model(net)
    model.prepare(
        optimizer.Adam(learning_rate=1e-3, parameters=net.parameters()),
        paddle.nn.CrossEntropyLoss(),
        Accuracy(),
    )
    rng = np.random.RandomState(0)
    xs = rng.randn(256, 1, 28, 28).astype(np.float32)
    ys = rng.randint(0, 10, (256, 1)).astype(np.int64)
    data = [(xs[i], ys[i]) for i in range(len(xs))]
    model.fit(data, batch_size=32, epochs=1, verbose=1)
    print("eval:", model.evaluate(data, batch_size=32, verbose=0))


if __name__ == "__main__":
    main()
