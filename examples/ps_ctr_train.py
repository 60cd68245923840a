"""Parameter-server CTR training (reference capability: Paddle's PS mode —
the_one_ps + MemorySparseTable for embedding tables bigger than device
memory).

Single command spawns the whole cluster locally over the PADDLE_* env
contract: 2 server processes hosting hash-sharded SparseTables, 2 trainer
processes running a wide&deep-style model — host-pulled sparse embeddings
feeding a device-side MLP — with raw row-gradients pushed back and the
sparse adagrad applied server-side (async-SGD composition across workers).

    JAX_PLATFORMS=cpu python examples/ps_ctr_train.py
"""
import os
import socket
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def role_main():
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu.distributed import ps

    role = ps.PsRoleMaker()
    if role.is_server():
        ps.init_server(role)
        ps.run_server(role)
        return

    client = ps.init_worker(role)
    paddle.seed(7 + role.worker_index)
    # 8 slots x 2000 ids = a 16k-id space here; the table grows lazily on
    # the servers, so only rows actually touched ever exist anywhere — the
    # same mechanics carry to production-scale (beyond-HBM) id spaces
    emb = ps.SparseEmbedding(client, "slots", 16, optimizer="adagrad", lr=0.05, seed=0)
    deep = nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 1))
    opt = optimizer.Adam(learning_rate=1e-2, parameters=deep.parameters())
    bce = nn.BCEWithLogitsLoss()

    rng = np.random.RandomState(role.worker_index)
    SLOT_VOCAB = 2000  # per-slot id range; slot s draws from [s*V, (s+1)*V)

    def is_hot(ids):
        # ~8% of the id space converts, spread uniformly so the signal must
        # be learned per-id, not read off the id's magnitude or frequency
        return (ids % 13) == 0

    def batch():
        ids = rng.randint(0, SLOT_VOCAB, (64, 8)).astype(np.int64)
        ids += np.arange(8, dtype=np.int64) * SLOT_VOCAB
        y = is_hot(ids).any(axis=1).astype(np.float32)[:, None]
        return ids, y

    for step in range(100):
        ids, y = batch()
        feats = emb(paddle.to_tensor(ids)).sum(axis=1)
        loss = bce(deep(feats), paddle.to_tensor(y))
        loss.backward()
        opt.step()
        opt.clear_grad()
        emb.push_grad()
        if step % 20 == 0 and role.is_first_worker():
            print(f"[worker0] step {step:3d} loss {float(loss.numpy()):.4f} "
                  f"table rows {client.table_len('slots')}", flush=True)

    # held-out eval
    correct = total = 0
    for _ in range(5):
        ids, y = batch()
        p = 1.0 / (1.0 + np.exp(-deep(emb(paddle.to_tensor(ids)).sum(axis=1)).numpy()))
        correct += ((p > 0.5) == (y > 0.5)).sum()
        total += y.size
        emb.discard()
    print(f"[worker{role.worker_index}] eval acc {correct / total:.3f}", flush=True)

    client.barrier("train_done", role.worker_num)
    if role.is_first_worker():
        st = client.state_dict("slots")
        print(f"[worker0] final table: {len(st['rows'])} rows "
              f"(sparse by construction — only touched ids exist)", flush=True)
    ps.stop_worker(role, client)


def launcher():
    def free_port():
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        p = s.getsockname()[1]
        s.close()
        return p

    ports = [free_port(), free_port()]
    eps = ",".join(f"127.0.0.1:{p}" for p in ports)
    base = {**os.environ, "PADDLE_PSERVERS_IP_PORT_LIST": eps,
            "PADDLE_TRAINERS_NUM": "2", "PYTHONPATH": REPO}
    procs = [subprocess.Popen(
        [sys.executable, __file__, "--role"],
        env={**base, "PADDLE_TRAINING_ROLE": "PSERVER", "PADDLE_PORT": str(p)})
        for p in ports]
    workers = [subprocess.Popen(
        [sys.executable, __file__, "--role"],
        env={**base, "PADDLE_TRAINING_ROLE": "TRAINER", "PADDLE_TRAINER_ID": str(w)})
        for w in range(2)]
    # poll the whole cluster: first nonzero exit tears everything down
    # (a crashed worker would otherwise leave its peer blocked in the
    # server-arbitrated barrier forever)
    import time

    everyone = procs + workers
    rc = 0
    deadline = time.monotonic() + 600
    while time.monotonic() < deadline:
        codes = [p.poll() for p in everyone]
        if any(c not in (None, 0) for c in codes):
            rc = next(c for c in codes if c not in (None, 0))
            print(f"PS cluster: a process failed (rc={rc}) — terminating peers")
            break
        if all(c == 0 for c in codes):
            break
        time.sleep(0.2)
    else:
        rc = rc or 1
        print("PS cluster: timeout — terminating")
    for p in everyone:
        if p.poll() is None:
            p.terminate()
    print("PS cluster exited", "OK" if rc == 0 else f"rc={rc}")
    sys.exit(rc)


if __name__ == "__main__":
    if "--role" in sys.argv:
        role_main()
    else:
        launcher()
