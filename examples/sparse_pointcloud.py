"""Sparse 3-D conv net on a synthetic point cloud (reference capability:
paddle.sparse.nn voxel CNNs — SubmConv3D/Conv3D/MaxPool3D over phi sparse
kernels).

    JAX_PLATFORMS=cpu python examples/sparse_pointcloud.py

Demonstrates: COO voxel input, a SubmConv3D -> MaxPool3D -> Conv3D stack
(host rulebook + device gather-GEMM-scatter, sparsity preserved end to
end), taped autodiff through the sparse containers, and a dense
classification head trained with the regular optimizer API.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import paddle_tpu as paddle
from paddle_tpu import nn, sparse


def make_cloud(rng, label, n_points=80, grid=16):
    """Two synthetic classes: points on a plane (0) vs on a sphere (1)."""
    if label == 0:
        xy = rng.uniform(0, grid, (n_points, 2))
        z = np.full((n_points, 1), grid // 2) + rng.randint(-1, 2, (n_points, 1))
        pts = np.concatenate([xy, z], 1)
    else:
        v = rng.randn(n_points, 3)
        v /= np.linalg.norm(v, axis=1, keepdims=True)
        pts = (grid / 2 - 1) * v + grid / 2
    vox = np.clip(pts.astype(np.int32), 0, grid - 1)
    vox, feat_rows = np.unique(vox, axis=0, return_index=True)
    feats = (pts[feat_rows] / grid).astype(np.float32)  # xyz as features
    return vox, feats


def batch_to_sparse(clouds, grid=16):
    idx, vals = [], []
    for b, (vox, feats) in enumerate(clouds):
        idx.append(np.concatenate([np.full((len(vox), 1), b), vox], 1))
        vals.append(feats)
    idx = np.concatenate(idx).T.astype(np.int32)  # [4, nnz]
    return sparse.sparse_coo_tensor(idx, np.concatenate(vals),
                                    (len(clouds), grid, grid, grid, 3))


class PointNetish(nn.Layer):
    def __init__(self, grid=16, num_classes=2):
        super().__init__()
        self.c1 = sparse.nn.SubmConv3D(3, 16, 3, padding=1)
        self.pool = sparse.nn.MaxPool3D(2, 2)
        self.c2 = sparse.nn.Conv3D(16, 32, 3, padding=1, stride=2)
        self.head = nn.Linear(32, num_classes)

    def forward(self, x):
        h = self.c2(sparse.relu(self.pool(sparse.relu(self.c1(x)))))
        B = h.shape[0]
        dense = h.to_dense()  # [B, g/4, g/4, g/4, 32], taped
        pooled = dense.reshape([B, -1, 32]).max(axis=1)  # global max pool
        return self.head(pooled)


def main():
    rng = np.random.RandomState(0)
    paddle.seed(7)
    model = PointNetish()
    opt = paddle.optimizer.Adam(learning_rate=2e-3, parameters=model.parameters())
    ce = nn.CrossEntropyLoss()

    for step in range(30):
        labels = rng.randint(0, 2, 8)
        x = batch_to_sparse([make_cloud(rng, l) for l in labels])
        logits = model(x)
        loss = ce(logits, paddle.to_tensor(labels.astype(np.int64)))
        loss.backward()
        opt.step()
        opt.clear_grad()
        if step % 10 == 0 or step == 29:
            pred = np.asarray(logits.numpy()).argmax(1)
            acc = (pred == labels).mean()
            print(f"step {step:3d}  loss {float(loss.numpy()):.4f}  acc {acc:.2f}  "
                  f"active sites: in {x.nnz()}")

    labels = rng.randint(0, 2, 32)
    x = batch_to_sparse([make_cloud(rng, l) for l in labels])
    pred = np.asarray(model(x).numpy()).argmax(1)
    print(f"eval acc over 32 fresh clouds: {(pred == labels).mean():.2f}")


if __name__ == "__main__":
    main()
