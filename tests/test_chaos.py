"""Fault-injection suite (ISSUE 1 tentpole): every recovery path the repo
claims is exercised here against a deterministic injected fault —
trainer killed mid-step, checkpoint shard truncated, store blackholed,
serving request failed — and must recover with BOUNDED retries and
unchanged training/serving semantics (resume-equivalence where applicable).
"""
import json
import os
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.testing import chaos
from paddle_tpu.utils.metrics_bus import counters

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_chaos():
    """Every test starts disarmed and leaves nothing armed behind."""
    chaos.disarm()
    yield
    chaos.disarm()


# ---------------------------------------------------------------------------
# the registry itself
# ---------------------------------------------------------------------------
class TestFaultPlan:
    def test_counting_after_times(self):
        plan = chaos.FaultPlan().fail("x.op", times=2, after=1)
        with plan:
            chaos.site("x.op")  # after=1: first hit passes
            for _ in range(2):
                with pytest.raises(chaos.FaultInjected):
                    chaos.site("x.op")
            chaos.site("x.op")  # times=2 exhausted: passes again
        assert plan.rules[0].fired == 2

    def test_glob_site_match(self):
        with chaos.FaultPlan().fail("store.*", times=1):
            with pytest.raises(chaos.FaultInjected):
                chaos.site("store.get")

    def test_seeded_probabilistic_is_deterministic(self):
        def run():
            fired = []
            with chaos.FaultPlan(seed=7).fail("p.op", times=None, p=0.5):
                for i in range(20):
                    try:
                        chaos.site("p.op")
                        fired.append(0)
                    except chaos.FaultInjected:
                        fired.append(1)
            return fired

        a, b = run(), run()
        assert a == b and 0 < sum(a) < 20

    def test_env_spec_round_trip(self):
        plan = (chaos.FaultPlan(seed=3)
                .fail("serve.decode", times=2, after=1)
                .exit("trainer.step", code=17, after=3))
        spec = plan.env_spec()
        assert chaos.parse_env_spec(spec, seed=3).env_spec() == spec

    def test_env_arming(self, monkeypatch):
        monkeypatch.setenv("PADDLE_CHAOS", "env.op:exc:times=1")
        chaos._ENV_PARSED = False  # fresh process simulation
        with pytest.raises(chaos.FaultInjected):
            chaos.site("env.op")
        chaos.site("env.op")  # exhausted
        chaos.disarm()

    def test_disabled_no_measurable_overhead(self):
        """With no plan armed, a site is a near-free no-op: the serve/train
        hot paths can carry the hook unconditionally. Generous absolute
        bound (1µs/call avg) so CI noise can't flake it; the disabled path
        is one global load + None check (~30ns in practice)."""
        chaos.site("warm.up")  # force the one-time env probe
        n = 200_000
        t0 = time.perf_counter()
        for _ in range(n):
            chaos.site("hot.path")
        dt = time.perf_counter() - t0
        assert dt / n < 1e-6, f"disabled chaos.site costs {dt / n * 1e9:.0f}ns/call"


# ---------------------------------------------------------------------------
# store blackhole -> bounded-backoff recovery
# ---------------------------------------------------------------------------
class TestStoreOutage:
    def test_store_ops_recover_within_retry_budget(self):
        from paddle_tpu.framework.native import TCPStore

        master = TCPStore("127.0.0.1", 0, is_master=True, use_native=False)
        client = TCPStore("127.0.0.1", master.port, use_native=False)
        counters.reset("fault.")
        # blackhole every op for (attempts-1) hits: each recovers on its
        # last try — the boundary of the budget
        with chaos.FaultPlan().fail("store.set", times=3).fail("store.get", times=3):
            client.set("k", b"v")
            assert client.get("k") == b"v"
        assert counters.get("fault.retry.store.set") == 3
        assert counters.get("fault.retry.store.get") == 3
        assert counters.get("fault.exhausted.store.set") == 0

        # one more failure than the budget -> bounded give-up, not a hang
        with chaos.FaultPlan().fail("store.add", times=None):
            with pytest.raises(ConnectionError):
                client.add("c", 1)
        assert counters.get("fault.exhausted.store.add") == 1
        master.stop_server()

    def test_rendezvous_survives_flaky_store(self):
        """A barrier (the launcher's rendezvous primitive) completes through
        transient per-op faults."""
        import threading

        from paddle_tpu.framework.native import TCPStore

        master = TCPStore("127.0.0.1", 0, is_master=True, use_native=False)
        clients = [master] + [TCPStore("127.0.0.1", master.port, use_native=False)
                              for _ in range(2)]
        errs = []
        with chaos.FaultPlan().fail("store.add", times=2).fail("store.check", times=2):

            def arrive(s):
                try:
                    s.barrier("chaos_b", 3, timeout=20)
                except Exception as e:  # noqa: BLE001
                    errs.append(e)

            ts = [threading.Thread(target=arrive, args=(s,)) for s in clients]
            [t.start() for t in ts]
            [t.join(30) for t in ts]
        assert not errs
        master.stop_server()


# ---------------------------------------------------------------------------
# PS RPC outage -> reconnect + retry (idempotent ops only)
# ---------------------------------------------------------------------------
class TestPsOutage:
    def test_pull_retries_push_fails_fast(self):
        from paddle_tpu.distributed.ps.service import PsClient, PsServer

        srv = PsServer().start()
        cli = PsClient([srv.endpoint])
        cli.create_table("emb", 4)
        ids = np.array([1, 2, 3], np.int64)
        counters.reset("fault.")
        with chaos.FaultPlan().fail("ps.call", times=2):
            rows = cli.pull("emb", ids)  # idempotent: retried to success
        assert rows.shape == (3, 4)
        assert counters.get("fault.retry.ps.pull") == 2

        with chaos.FaultPlan().fail("ps.call", times=1):
            with pytest.raises(ConnectionError):
                # push is not idempotent: NO transparent resend
                cli.push("emb", ids, np.ones((3, 4), np.float32))
        # the dropped connection redials on the next call
        assert cli.pull("emb", ids).shape == (3, 4)
        cli.stop_servers()
        cli.close()
        srv.stop()

    def test_authkey_from_env(self, monkeypatch):
        from paddle_tpu.distributed.ps import service

        monkeypatch.setenv("PADDLE_PS_AUTHKEY", "cluster-secret-1")
        assert service._authkey() == b"cluster-secret-1"
        srv = service.PsServer().start()
        cli = service.PsClient([srv.endpoint])
        assert cli.ping() == ["pong"]
        cli.close()
        # a client with the WRONG key is rejected by connection auth
        monkeypatch.setenv("PADDLE_PS_AUTHKEY", "wrong-secret")
        bad = service.PsClient([srv.endpoint], connect_timeout=2.0)
        with pytest.raises(Exception):
            bad.ping()
        bad.close()
        monkeypatch.setenv("PADDLE_PS_AUTHKEY", "cluster-secret-1")
        srv.stop()


# ---------------------------------------------------------------------------
# checkpoint: atomic commit + truncated-shard detection + resume equivalence
# ---------------------------------------------------------------------------
class TestCheckpointFaults:
    def _sd(self, val):
        return {"w": paddle.to_tensor(np.full((4, 3), val, np.float32)),
                "b": paddle.to_tensor(np.arange(3, dtype=np.float32) * val)}

    def test_mid_write_death_keeps_previous_checkpoint(self, tmp_path):
        from paddle_tpu.distributed.checkpoint import load_state_dict, save_state_dict

        path = str(tmp_path / "ckpt")
        save_state_dict(self._sd(1.0), path)
        with chaos.FaultPlan().fail("ckpt.write"):
            with pytest.raises(ConnectionError):
                save_state_dict(self._sd(2.0), path)
        tgt = self._sd(0.0)
        load_state_dict(tgt, path)  # previous checkpoint intact
        np.testing.assert_array_equal(tgt["w"].numpy(), np.full((4, 3), 1.0))
        assert not [f for f in os.listdir(path) if ".tmp" in f], \
            "failed save must not leave temp litter"

    def test_truncated_shard_detected_before_any_load(self, tmp_path):
        from paddle_tpu.distributed.checkpoint import (
            CheckpointCorruptError, load_state_dict, save_state_dict)

        path = str(tmp_path / "ckpt")
        save_state_dict(self._sd(3.0), path)
        shard = next(str(tmp_path / "ckpt" / f) for f in os.listdir(path)
                     if f.endswith(".distcp.npz"))
        keep = os.path.getsize(shard) // 2
        with open(shard, "rb+") as f:
            f.truncate(keep)
        tgt = self._sd(0.0)
        with pytest.raises(CheckpointCorruptError):
            load_state_dict(tgt, path)
        # integrity gate fired BEFORE mutating any tensor
        np.testing.assert_array_equal(tgt["w"].numpy(), np.zeros((4, 3)))
        assert counters.get("fault.ckpt.corrupt_shard") >= 1

    def test_injected_truncation_caught_by_manifest_crc(self, tmp_path):
        from paddle_tpu.distributed.checkpoint import (
            CheckpointCorruptError, load_state_dict, save_state_dict)

        path = str(tmp_path / "ckpt")
        with chaos.FaultPlan().truncate("ckpt.write", keep_bytes=64):
            save_state_dict(self._sd(4.0), path)
        with pytest.raises(CheckpointCorruptError):
            load_state_dict(self._sd(0.0), path)

    def test_async_save_failure_surfaces_on_wait(self, tmp_path):
        from paddle_tpu.distributed.checkpoint import save_state_dict

        with chaos.FaultPlan().fail("ckpt.write"):
            h = save_state_dict(self._sd(5.0), str(tmp_path / "c2"), async_save=True)
            with pytest.raises(ConnectionError):
                h.wait(timeout=30)

    def test_uninterrupted_equals_crash_resume(self, tmp_path):
        """Semantic preservation: train 6 steps straight == train 3, die at
        an injected save-path fault, reload the surviving checkpoint, train
        3 more (the resume-equivalence contract under injected faults)."""
        from paddle_tpu import optimizer as optim

        def build():
            paddle.seed(0)
            net = paddle.nn.Linear(4, 4)
            opt = optim.SGD(learning_rate=0.1, parameters=net.parameters())
            return net, opt

        x = paddle.to_tensor(np.ones((2, 4), np.float32))

        def step(net, opt):
            loss = (net(x) ** 2).sum()
            loss.backward()
            opt.step()
            opt.clear_grad()

        net_ref, opt_ref = build()
        for _ in range(6):
            step(net_ref, opt_ref)
        ref = {k: np.asarray(v._data) for k, v in net_ref.state_dict().items()}

        net, opt = build()
        mpath = str(tmp_path / "m.pdparams")
        for _ in range(3):
            step(net, opt)
        paddle.save(net.state_dict(), mpath)
        paddle.save(opt.state_dict(), str(tmp_path / "o.pdopt"))
        # a later save dies mid-write: file must still hold the step-3 state
        with chaos.FaultPlan().fail("save.write"):
            step(net, opt)  # step 4 happens but its checkpoint is lost
            with pytest.raises(ConnectionError):
                paddle.save(net.state_dict(), mpath)

        net2, opt2 = build()
        net2.set_state_dict(paddle.load(mpath))
        opt2.set_state_dict(paddle.load(str(tmp_path / "o.pdopt")))
        for _ in range(3):  # redo steps 4..6
            step(net2, opt2)
        out = {k: np.asarray(v._data) for k, v in net2.state_dict().items()}
        for k in ref:
            np.testing.assert_allclose(out[k], ref[k], atol=1e-6)


# ---------------------------------------------------------------------------
# trainer killed mid-step -> launcher restart -> autoresume
# ---------------------------------------------------------------------------
class TestTrainerKill:
    TRAIN_BODY = """
    import os, sys
    sys.path.insert(0, {repo!r})
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import optimizer as optim
    from paddle_tpu.distributed.fleet.elastic import autoresume
    from paddle_tpu.testing import chaos

    paddle.seed(0)
    net = paddle.nn.Linear(4, 4)
    opt = optim.SGD(learning_rate=0.1, parameters=net.parameters())
    x = paddle.to_tensor(np.ones((2, 4), np.float32))

    def train(start_step, save_cb):
        for step in range(start_step, 8):
            chaos.site("trainer.step")   # injected kill lands HERE
            loss = (net(x) ** 2).sum()
            loss.backward()
            opt.step()
            opt.clear_grad()
            save_cb(step + 1)
        return float(loss.numpy())

    autoresume(train, "ckpt", model=net, optimizer=opt, max_attempts=2)
    w = net.state_dict()["weight"].numpy()
    np.save("final_w.npy", w)
    """

    def _run(self, tmp_path, extra_env, extra_args=()):
        os.makedirs(tmp_path, exist_ok=True)
        script = tmp_path / "worker.py"
        script.write_text(textwrap.dedent(self.TRAIN_BODY).format(repo=REPO))
        env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO,
               **extra_env}
        cmd = [sys.executable, "-m", "paddle_tpu.distributed.launch",
               "--nproc_per_node", "1", "--log_dir", str(tmp_path / "logs"),
               *extra_args, str(script)]
        return subprocess.run(cmd, env=env, cwd=str(tmp_path),
                              capture_output=True, text=True, timeout=240)

    def test_kill_mid_step_restart_resumes_equivalently(self, tmp_path):
        # reference run, no chaos
        r = self._run(tmp_path / "ref", {"PADDLE_CHAOS": ""})
        assert r.returncode == 0, r.stdout + r.stderr
        ref_w = np.load(tmp_path / "ref" / "final_w.npy")

        # chaos run: hard-kill (os._exit(9)) the trainer at step 4 of the
        # first attempt; elastic watch restarts it; autoresume reloads the
        # step-3 checkpoint and finishes. Exit-code 9 is a CRASH, so this
        # also exercises the elastic_level>=1 restart budget path.
        r2 = self._run(tmp_path / "chaos",
                       {"PADDLE_CHAOS": "trainer.step:exit=9:after=3:times=1"},
                       extra_args=("--elastic_level", "1"))
        assert r2.returncode == 0, r2.stdout + r2.stderr + _logs(tmp_path / "chaos")
        out_w = np.load(tmp_path / "chaos" / "final_w.npy")
        np.testing.assert_allclose(out_w, ref_w, atol=1e-6)

    def test_preemption_sigterm_checkpoints_and_restarts(self, tmp_path):
        """SIGTERM mid-training: the trainer checkpoints at the next save
        boundary, exits PREEMPTED_EXIT_CODE, and the watch loop restarts it
        even WITHOUT elastic_level — preemption is not a crash."""
        script = tmp_path / "worker.py"
        script.write_text(textwrap.dedent("""
        import json, os, signal, sys, time
        sys.path.insert(0, {repo!r})
        import numpy as np
        import paddle_tpu as paddle
        from paddle_tpu import optimizer as optim
        from paddle_tpu.distributed.fleet.elastic import autoresume

        paddle.seed(0)
        net = paddle.nn.Linear(4, 4)
        opt = optim.SGD(learning_rate=0.1, parameters=net.parameters())
        x = paddle.to_tensor(np.ones((2, 4), np.float32))

        def train(start_step, save_cb):
            for step in range(start_step, 8):
                if step == 3 and not os.path.exists("preempted_once"):
                    open("preempted_once", "w").write("1")
                    os.kill(os.getpid(), signal.SIGTERM)  # platform preempts us
                loss = (net(x) ** 2).sum()
                loss.backward()
                opt.step()
                opt.clear_grad()
                save_cb(step + 1)
            return float(loss.numpy())

        autoresume(train, "ckpt", model=net, optimizer=opt)
        open("done", "w").write("ok")
        """).format(repo=REPO))
        env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}
        cmd = [sys.executable, "-m", "paddle_tpu.distributed.launch",
               "--nproc_per_node", "1", "--log_dir", str(tmp_path / "logs"),
               str(script)]
        r = subprocess.run(cmd, env=env, cwd=str(tmp_path),
                           capture_output=True, text=True, timeout=240)
        assert r.returncode == 0, r.stdout + r.stderr + _logs(tmp_path)
        assert (tmp_path / "done").exists()
        # the preemption really checkpointed: resume marker reached step 8
        meta = json.loads((tmp_path / "ckpt" / "resume.json").read_text())
        assert meta["step"] == 8

    def test_restart_budget_bounds_crash_loop(self, tmp_path):
        """A deterministic crasher must exhaust --max_restart and abort,
        not respawn forever."""
        script = tmp_path / "worker.py"
        script.write_text("import sys; sys.exit(5)\n")
        env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}
        t0 = time.time()
        r = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nproc_per_node", "1", "--log_dir", str(tmp_path / "logs"),
             "--elastic_level", "1", "--max_restart", "2", str(script)],
            env=env, cwd=str(tmp_path), capture_output=True, text=True, timeout=120)
        assert r.returncode == 1
        assert time.time() - t0 < 60


# ---------------------------------------------------------------------------
# dataloader worker death -> bounded respawn, order preserved
# ---------------------------------------------------------------------------
class TestDataloaderWorkerDeath:
    def test_worker_killed_mid_epoch_respawns_and_preserves_batches(self):
        from paddle_tpu.io import DataLoader, Dataset

        class Ds(Dataset):
            def __len__(self):
                return 20

            def __getitem__(self, i):
                return np.full((3,), i, np.float32)

        dl = DataLoader(Ds(), batch_size=2, num_workers=2, shuffle=False)
        ref = [b.numpy() for b in DataLoader(Ds(), batch_size=2, shuffle=False)]
        counters.reset("fault.")
        # chaos hit-counting is per-process: EACH first-generation worker
        # (5 batches apiece) dies at its 4th batch; the respawned workers
        # (2 batches owed apiece) never reach the after=3 threshold
        with chaos.FaultPlan().exit("dataloader.worker", code=9, after=3, times=1):
            out = [b.numpy() for b in dl]
        assert len(out) == len(ref)
        for o, r in zip(out, ref):
            np.testing.assert_array_equal(o, r)
        assert counters.get("fault.dataloader_respawn") == 2

    def test_persistent_crasher_exhausts_respawns(self):
        from paddle_tpu.io import DataLoader, Dataset

        class Ds(Dataset):
            def __len__(self):
                return 12

            def __getitem__(self, i):
                return np.full((2,), i, np.float32)

        dl = DataLoader(Ds(), batch_size=1, num_workers=1, shuffle=False)
        with chaos.FaultPlan().exit("dataloader.worker", code=9, times=None):
            with pytest.raises(RuntimeError, match="respawns exhausted"):
                list(dl)


# ---------------------------------------------------------------------------
# serving: request failure isolation, decode outage, deadlines, stale-weights
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tiny_engine_setup():
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

    paddle.seed(0)
    cfg = llama_tiny(num_hidden_layers=2)
    model = LlamaForCausalLM(cfg)
    rng = np.random.RandomState(11)
    prompts = [rng.randint(1, cfg.vocab_size, (l,)).astype(np.int32)
               for l in (5, 9, 7)]
    return model, prompts


def _engine(model, **kw):
    from paddle_tpu.inference.continuous import ContinuousBatchingEngine

    kw.setdefault("max_seqs", 2)
    kw.setdefault("page_size", 16)
    kw.setdefault("max_len", 64)
    return ContinuousBatchingEngine(model, **kw)


class TestServingFaults:
    def test_failed_prefill_retires_slot_not_batch(self, tiny_engine_setup):
        """A prompt's chunk rides a mixed dispatch beside its co-tenants'
        decode rows; when that dispatch runs out of memory (the chunk is
        what sizes it) the PROMPT fails alone and the decode row goes out
        again with the next step."""
        model, prompts = tiny_engine_setup
        eng = _engine(model, decode_block=4)
        new = [4, 12, 4]  # rid 1 still decodes when rid 2 is admitted
        ref = eng.serve(prompts, max_new_tokens=new)
        counters.reset("fault.")
        mixed, fired = eng._dispatch_ragged_mixed, []

        def oom_beside_a_decode_row(chain):
            if eng._active and not fired:
                fired.append(sorted(eng._prefilling))
                with chaos.FaultPlan().fail("obs.oom", times=1):
                    return mixed(chain)
            return mixed(chain)

        eng._dispatch_ragged_mixed = oom_beside_a_decode_row
        outs = eng.serve(prompts, max_new_tokens=new)
        del eng._dispatch_ragged_mixed
        assert len(fired[0]) == 1  # one prompt's chunk was in that dispatch
        assert outs[2] is None
        assert isinstance(eng.request_errors[2], chaos.FaultInjected)
        assert eng.stats["failed_requests"] == 1
        # co-tenants unaffected AND semantics preserved exactly
        np.testing.assert_array_equal(outs[0], ref[0])
        np.testing.assert_array_equal(outs[1], ref[1])
        # no leaked pages/slots: the warm engine serves the full set again
        assert len(eng.free_pages) == eng.num_pages - 1
        assert sorted(eng.free_slots) == [0, 1]
        outs2 = eng.serve(prompts, max_new_tokens=new)
        for o, r in zip(outs2, ref):
            np.testing.assert_array_equal(o, r)

    def test_transient_decode_outage_bounded_retry(self, tiny_engine_setup):
        model, prompts = tiny_engine_setup
        eng = _engine(model)
        ref = eng.serve(prompts, max_new_tokens=4)
        counters.reset("fault.")
        with chaos.FaultPlan().fail("serve.decode", times=2):
            outs = eng.serve(prompts, max_new_tokens=4)
        for o, r in zip(outs, ref):
            np.testing.assert_array_equal(o, r)  # retries change NOTHING
        assert counters.get("fault.retry.serve.decode") == 2

    def test_persistent_decode_outage_gives_up_cleanly(self, tiny_engine_setup):
        model, prompts = tiny_engine_setup
        eng = _engine(model)
        with chaos.FaultPlan().fail("serve.decode", times=None):
            with pytest.raises(ConnectionError):
                eng.serve(prompts, max_new_tokens=4)
        # cleanup freed everything; engine still usable
        assert len(eng.free_pages) == eng.num_pages - 1
        assert eng.serve(prompts[:1], max_new_tokens=2)[0] is not None

    def test_oversized_request_fails_alone(self, tiny_engine_setup):
        model, prompts = tiny_engine_setup
        rng = np.random.RandomState(3)
        eng = _engine(model)
        big = rng.randint(1, model.config.vocab_size, (40,)).astype(np.int32)
        outs = eng.serve([big, prompts[0]], max_new_tokens=30)
        assert outs[0] is None
        assert isinstance(eng.request_errors[0], ValueError)
        assert outs[1] is not None and len(outs[1]) == len(prompts[0]) + 30

    def test_pool_impossible_request_fails_alone(self, tiny_engine_setup):
        model, prompts = tiny_engine_setup
        rng = np.random.RandomState(4)
        eng = _engine(model, num_pages=3)  # 2 real pages = 32 tokens
        p20 = rng.randint(1, model.config.vocab_size, (20,)).astype(np.int32)
        outs = eng.serve([p20, prompts[0]], max_new_tokens=20)
        assert outs[0] is None and "more pages" in str(eng.request_errors[0])
        assert outs[1] is not None

    def test_request_deadline_returns_partial(self, tiny_engine_setup):
        model, prompts = tiny_engine_setup
        # async_decode=False: the deadline sweep runs after the step's
        # readback, so the partial holds the first token; under the async
        # pipeline the block is still in flight at the sweep and an instant
        # deadline returns the prompt alone (tests/test_ragged_attention.py)
        eng = _engine(model, max_seqs=1, decode_block=1, async_decode=False)
        outs = eng.serve([prompts[0]], max_new_tokens=30, request_timeout_s=0.0)
        assert eng.stats["timed_out_requests"] == 1
        # partial result: the prompt plus at least the first token
        assert outs[0] is not None
        assert len(prompts[0]) < len(outs[0]) < len(prompts[0]) + 30

    def test_weight_update_invalidates_prefix_cache(self, tiny_engine_setup):
        """The monotonic mutation counter (not id()) clears cached prefix
        KV on any set_value/load — recycled array addresses can't alias."""
        model, _ = tiny_engine_setup
        rng = np.random.RandomState(5)
        shared = rng.randint(1, model.config.vocab_size, (32,)).astype(np.int32)
        mk = lambda tail: np.concatenate([shared, tail]).astype(np.int32)
        eng = _engine(model, max_seqs=2, max_len=128, enable_prefix_cache=True)
        p1 = mk(rng.randint(1, model.config.vocab_size, (4,)))
        p2 = mk(rng.randint(1, model.config.vocab_size, (5,)))
        eng.serve([p1], max_new_tokens=2)
        eng.serve([p2], max_new_tokens=2)
        assert eng.stats["prefix_hit_pages"] > 0  # cache worked
        # in-place weight mutation (same object, same id) must invalidate
        w = next(iter(model.parameters()))
        w.set_value(paddle.Tensor(np.asarray(w._data) * 1.0))
        hits_before = eng.stats["prefix_hit_pages"]
        eng.serve([p2], max_new_tokens=2)
        assert eng.stats["prefix_hit_pages"] == hits_before, \
            "stale prefix KV served after a weight update"
        # a DIRECT _data rebind (the optimizer epilogues' pattern, no
        # set_value) must also invalidate — the id-tuple factor catches it
        # even without a counter bump
        eng.serve([p2], max_new_tokens=2)  # re-warm the cache
        w._data = w._data * 1.0
        hits_before = eng.stats["prefix_hit_pages"]
        eng.serve([p2], max_new_tokens=2)
        assert eng.stats["prefix_hit_pages"] == hits_before, \
            "stale prefix KV served after a direct weight rebind"

    def test_optimizer_step_bumps_mutation_version(self):
        """The optimizer writes params via direct _data rebind; the
        weight-cache mutation counter must tick anyway (review finding:
        the counter alone would otherwise miss every training step)."""
        from paddle_tpu import optimizer as optim
        from paddle_tpu.framework import core

        net = paddle.nn.Linear(3, 3)
        opt = optim.SGD(learning_rate=0.1, parameters=net.parameters())
        loss = (net(paddle.to_tensor(np.ones((2, 3), np.float32))) ** 2).sum()
        loss.backward()
        v0 = core.tensor_mutation_version()
        opt.step()
        assert core.tensor_mutation_version() > v0


def _logs(tmp_path):
    out = []
    logs = tmp_path / "logs"
    if logs.is_dir():
        for f in logs.iterdir():
            out.append(f"--- {f.name}\n{f.read_text()[-2000:]}")
    return "\n".join(out)
