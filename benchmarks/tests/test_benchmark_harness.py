"""Run by hand: `pytest benchmarks/tests -q` (tier-1 collects tests/ only).

What lets a later PR bring a configuration of another architecture by new
files alone: a configuration file names its builder and its reference, a
workload file names the kernel tiers its `correct` demands, and `--check`
reads all of them. The last test adds such a configuration to a copy of the
benchmark, touching no file that was there, and rehearses its cell."""
import hashlib
import json
import os
import shutil
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import model  # noqa: E402
from benchmarks import run as bench  # noqa: E402

CONFIG_FILES = ("deepseek-llm-7b", "mistral-7b-v0.3", "mistral-7b-v0.3-mp2z2")
#: sha256 of json.dumps(sort_keys=True) of what the parent's
#: model.load_config(name, rehearse) gave (e065033), keyed name|rehearse
PARENT_CONFIG_SHA = {
    "deepseek-llm-7b|0":
        "e4c7034f39d360af1809ed878abcaf74a6ff5e616222796dd2e7abb60809cf4a",
    "deepseek-llm-7b|1":
        "3cc6d4b6f89b878234401d4620cce08929d58ea63f27e67d61bb17e8c6869f80",
    "mistral-7b-v0.3|0":
        "a852b3b072aa39f9859a7fbad5d23fb531f77e0e359b3ba08eb2e1a14e895ddd",
    "mistral-7b-v0.3|1":
        "35f2cf4fb398290b0ae63e92a430beeba454e6283839edfd451f51b697c05c72",
    "mistral-7b-v0.3-mp2z2|0":
        "b9ba361b4a792a998cc9f89a7006e61aabe399ee71fb8546b4fdf7eadc10b7ca",
    "mistral-7b-v0.3-mp2z2|1":
        "2002ed402c2afc9e0b851f603a231fdae3486d93eed8bebfd0e623c0d928def3",
}
#: the numbers a failure of the hash should be read against:
#: (layers, heads, kv heads, ffn, vocab) as run, then under --rehearse
PARENT_WIDTHS = {
    "deepseek-llm-7b": ((7, 32, 32, 11008, 102400), (2, 4, 4, 512, 512)),
    "mistral-7b-v0.3": ((4, 32, 8, 14336, 32768), (2, 4, 1, 512, 512)),
    "mistral-7b-v0.3-mp2z2": ((8, 32, 8, 14336, 32768), (2, 4, 1, 512, 512)),
}


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.mark.parametrize("rehearse", [False, True])
@pytest.mark.parametrize("name", CONFIG_FILES)
def test_llama_builder_gives_the_parents_dictionary(name, rehearse):
    """The two keys that name the modules are all the files gained."""
    raw = _json(ROOT, "benchmarks", "configs", name + ".json")
    assert (raw["builder"], raw["reference"]) == ("model", "reference")
    cfg = model.load_config(raw, rehearse)
    assert raw == _json(ROOT, "benchmarks", "configs", name + ".json")
    assert tuple(cfg[k] for k in (
        "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
        "intermediate_size", "vocab_size")) == PARENT_WIDTHS[name][rehearse]
    assert cfg["hidden_size"] == cfg["num_attention_heads"] * cfg["head_dim"]
    as_parent = {k: v for k, v in cfg.items()
                 if k not in ("builder", "reference")}
    assert hashlib.sha256(json.dumps(as_parent, sort_keys=True).encode()
                          ).hexdigest() == PARENT_CONFIG_SHA[
                              f"{name}|{int(rehearse)}"]


# --- the tiers `correct` demands -----------------------------------------

def _tiers(cell, rehearse=False):
    ctx = types.SimpleNamespace(
        cell=_json(ROOT, "benchmarks", "workloads", cell + ".json"),
        rehearse=rehearse)
    return bench.Ctx.tiers(ctx)


def _set_impls(monkeypatch, cell, **other):
    """Every module the cell names reads what the cell wants, but `other`
    (key of `checks` -> what ran instead)."""
    import importlib

    spec = _json(ROOT, "benchmarks", "workloads", cell + ".json")["tiers"]
    for key, tier in spec.items():
        monkeypatch.setattr(importlib.import_module(tier["module"]),
                            "LAST_IMPL", other.get(key, tier["want"]))


@pytest.mark.parametrize("cell,key,fallback", [
    ("deepseek7b-chat-steady", "ragged_impl", "ragged-math"),
    ("deepseek7b-chat-steady", "paged_impl", "paged-math"),
    ("mistral7b-pretrain-4k", "flash_impl", "xla"),
    ("mistral7b-pretrain-4k", "flash_impl", "pallas"),
    ("mistral7b-pretrain-4k-mp2z2", "flash_impl", "xla"),
])
def test_a_cell_on_a_fallback_tier_is_not_correct(monkeypatch, cell, key,
                                                  fallback):
    _set_impls(monkeypatch, cell, **{key: fallback})
    checks, problems = _tiers(cell)
    assert checks[key] == fallback
    assert len(problems) == 1 and problems[0].startswith(key + ":")
    assert repr(fallback) in problems[0]
    # a rehearsal runs off the chip: it records the tier and demands nothing
    assert _tiers(cell, rehearse=True) == (checks, [])


@pytest.mark.parametrize("cell,keys", [
    ("deepseek7b-chat-steady", {"ragged_impl": "ragged-kernel",
                                "paged_impl": "paged-kernel"}),
    ("mistral7b-pretrain-4k", {"flash_impl": "splash"}),
])
def test_the_accepted_cells_demand_what_they_demanded(monkeypatch, cell, keys):
    """The keys of `checks` and the tiers are the parent's (e065033:
    serve_openloop.py:136,164-167, train_steps.py:117,132-135)."""
    _set_impls(monkeypatch, cell)
    assert _tiers(cell) == (keys, [])


# --- --check, on copies of the benchmark ---------------------------------

@pytest.fixture
def copy(tmp_path, monkeypatch):
    """A copy of benchmarks/ and BENCHMARK.json with run.py's `check`
    pointed at it; the program is linked in beside it."""
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    os.symlink(os.path.join(ROOT, "paddle_tpu"), tmp_path / "paddle_tpu")
    monkeypatch.setattr(bench, "ROOT", str(tmp_path))
    monkeypatch.setattr(bench, "HERE", str(tmp_path / "benchmarks"))
    return tmp_path


def _edit(path, change):
    spec = _json(path)
    change(spec)
    with open(path, "w") as f:
        json.dump(spec, f, indent=1)


def test_check_passes_on_the_real_manifest(copy):
    assert bench.check(bench.manifest()) == []


def test_check_names_a_cell_without_demanded_tiers(copy):
    _edit(copy / "benchmarks" / "workloads" / "deepseek7b-chat-steady.json",
          lambda spec: spec.pop("tiers"))
    faults = bench.check(bench.manifest())
    assert len(faults) == 1
    assert "deepseek7b-chat-steady" in faults[0] and "tiers" in faults[0]


def test_check_names_a_reduced_key_without_its_published_value(copy):
    _edit(copy / "benchmarks" / "configs" / "mistral-7b-v0.3.json",
          lambda spec: spec.update(published={}))
    faults = bench.check(bench.manifest())
    assert len(faults) == 1
    assert "mistral-7b-v0.3" in faults[0]
    assert "num_hidden_layers" in faults[0] and "published" in faults[0]


def test_check_names_a_builder_that_does_not_exist(copy):
    _edit(copy / "benchmarks" / "configs" / "deepseek-llm-7b.json",
          lambda spec: spec.update(builder="no_such_builder"))
    faults = bench.check(bench.manifest())
    assert len(faults) == 1
    assert "deepseek-llm-7b" in faults[0] and "no_such_builder" in faults[0]


def test_check_names_a_source_that_differs(copy):
    _edit(copy / "benchmarks" / "configs" / "deepseek-llm-7b.json",
          lambda spec: spec.update(source="https://example.org/other"))
    faults = bench.check(bench.manifest())
    assert len(faults) == 1 and "source" in faults[0]


def test_check_names_a_configuration_file_that_does_not_parse(copy):
    with open(copy / "benchmarks" / "configs" / "mistral-7b-v0.3.json",
              "w") as f:
        f.write("{")
    faults = bench.check(bench.manifest())
    assert len(faults) == 1 and "mistral-7b-v0.3.json" in faults[0]


# --- a configuration of another architecture, by new files alone ---------

STANDIN_CONFIG = {
    "source": "https://example.org/standin/latent-experts/config.json",
    "builder": "standin_builder",
    "reference": "standin_reference",
    "hidden_size": 1024,
    "num_attention_heads": 16,
    "kv_lora_rank": 256,
    "q_lora_rank": 384,
    "qk_nope_head_dim": 64,
    "qk_rope_head_dim": 32,
    "v_head_dim": 64,
    "intermediate_size": 4096,
    "moe_intermediate_size": 512,
    "n_routed_experts": 8,
    "num_experts_per_tok": 2,
    "first_k_dense_replace": 1,
    "num_hidden_layers": 3,
    "vocab_size": 8192,
    "torch_dtype": "bfloat16",
    "published": {"num_hidden_layers": 24, "n_routed_experts": 64,
                  "vocab_size": 65536},
    "reduced": {"num_hidden_layers": "24 -> 3", "n_routed_experts": "64 -> 8",
                "vocab_size": "65536 -> 8192"},
}

STANDIN_BUILDER = '''\
"""Builder of a stand-in family: the keys of a latent-attention decoder
with routed experts, and no head_dim. What the engine can serve today is
the dense decoder, so that is what `build` hands it, at the stand-in's own
tiny widths; a real family brings a program class of its own."""

LAST_IMPL = None  # the stand-in's own kernel tier, demanded by its cell

OWN_KEYS = ("kv_lora_rank", "q_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "n_routed_experts",
            "num_experts_per_tok", "first_k_dense_replace")
TINY = {"hidden_size": 128, "num_attention_heads": 2, "kv_lora_rank": 32,
        "q_lora_rank": 48, "qk_nope_head_dim": 32, "qk_rope_head_dim": 32,
        "v_head_dim": 32, "intermediate_size": 256,
        "moe_intermediate_size": 64, "n_routed_experts": 4,
        "num_hidden_layers": 2, "vocab_size": 256}


def load_config(raw, rehearse=False):
    cfg = dict(raw)
    if rehearse:
        cfg.update(TINY)
    missing = [k for k in OWN_KEYS if k not in cfg]
    if missing or "head_dim" in cfg:
        raise ValueError(f"not this family's configuration: {missing}")
    return cfg


def build(cfg, seed, train, max_len, rehearse=False, recompute=False):
    global LAST_IMPL
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    paddle.seed(int(seed) % (2 ** 31 - 1))
    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_attention_heads"],
        max_position_embeddings=max_len, dtype="float32"))
    model.eval()
    LAST_IMPL = "standin-kernel"
    return model
'''

STANDIN_REFERENCE = '''\
"""Reference of the stand-in family: the served tokens against the argmax
of a forward of its own over each whole row."""
import numpy as np


class Wrong(Exception):
    pass


def check_served(model, prompts, outs):
    import paddle_tpu as paddle

    checked = exact = 0
    for prompt, out in zip(prompts, outs):
        out = np.asarray(out, np.int32)
        logits = np.asarray(model(paddle.to_tensor(out[None, :]))._data)[0]
        best = logits.argmax(-1)
        for pos in range(len(prompt), len(out)):
            checked += 1
            exact += int(best[pos - 1] == out[pos])
    if exact < 0.9 * checked:
        raise Wrong(f"{exact} of {checked} served tokens are the argmax")
    return {"checked": checked, "exact": exact}
'''

STANDIN_READER = '''\
"""Reader of the stand-in's own per-layer metric."""


def finished_requests(ctx):
    if ctx.result["kind"] != "serve":
        return None
    return sum(1 for r in ctx.result["requests"]
               if r["measured"] and r["t_done"] is not None) or None
'''


def _hashes(top):
    out = {}
    for root, dirs, files in os.walk(top):
        dirs[:] = [d for d in dirs if d not in ("__pycache__", ".jax_cache",
                                                ".bench_out", "out")]
        for name in files:
            path = os.path.join(root, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, top)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


def test_a_configuration_of_another_architecture_by_new_files_alone(copy):
    """What `git status` of the copy would show: BENCHMARK.json modified
    (entries added), every other file that was there untouched, eight files
    added. Then --check, the context and a CPU rehearsal of the new cell."""
    raw = STANDIN_CONFIG
    with pytest.raises(KeyError, match="head_dim"):
        model.load_config(raw)  # the Llama builder cannot take it
    before = _hashes(copy)
    b = copy / "benchmarks"
    new = {
        b / "configs" / "standin-latent-experts.json": json.dumps(raw),
        b / "standin_builder.py": STANDIN_BUILDER,
        b / "standin_reference.py": STANDIN_REFERENCE,
        b / "readers" / "standin.py": STANDIN_READER,
        b / "workloads" / "standin-chat.json": json.dumps({
            "config": "standin-latent-experts", "traffic": "chat-steady",
            "chips": 1, "runner": "serve_openloop",
            "engine": {"ragged": True, "max_seqs": 4, "page_size": 16,
                       "max_len": 256, "prefill_chunk": 32, "decode_block": 4,
                       "enable_prefix_cache": False},
            "tiers": {"standin_impl": {"module": "benchmarks.standin_builder",
                                       "want": "standin-kernel"}},
            "why": "the stand-in family through the accepted serving runner",
        }),
        b / "metrics" / "standin.finished_requests.json": json.dumps({
            "unit": "requests", "layer": "whole request",
            "moves": "serve_tok_per_s",
            "reader": "standin:finished_requests", "args": {}}),
    }
    for path, text in new.items():
        assert not path.exists()
        path.write_text(text)
    man = bench.manifest()
    man["configs"].append({
        "name": "standin-latent-experts", "source": raw["source"],
        "file": "benchmarks/configs/standin-latent-experts.json",
        "reduced": ["num_hidden_layers", "n_routed_experts", "vocab_size"],
        "why": "a stand-in: latent attention and routed experts, no head_dim"})
    man["workloads"].append({
        "name": "standin-chat", "config": "standin-latent-experts",
        "traffic": "chat-steady", "chips": 1,
        "why": "the stand-in family under the accepted chat mix"})
    for m in man["end_to_end"]:
        if m["name"] in ("ttft_mean_ms", "tpot_p90_ms", "serve_tok_per_s"):
            m["workloads"].append("standin-chat")
    man["per_layer"].append({
        "name": "standin.finished_requests", "unit": "requests",
        "better": "higher", "source": "program_counter",
        "layer": "whole request", "moves": "serve_tok_per_s",
        "workloads": ["standin-chat"]})
    (copy / "BENCHMARK.json").write_text(json.dumps(man, indent=1))

    after = _hashes(copy)
    changed = sorted(k for k in before if after[k] != before[k])
    assert changed == ["BENCHMARK.json"]
    assert sorted(set(after) - set(before)) == sorted(
        os.path.relpath(p, copy) for p in new)

    assert bench.check(bench.manifest()) == []

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, str(b / "run.py"), "--workload", "standin-chat",
         "--seed", "2147483659", "--seconds", "4", "--trace", "0",
         "--rehearse", "--out", str(copy / "out")],
        cwd=copy, env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "rehearsal passed" in r.stdout
    phases = [json.loads(line) for line in r.stdout.splitlines()
              if line.startswith('{"phase"')]
    metrics = next(p for p in phases if p["phase"] == "metrics")
    assert metrics["problems"] == []
    assert metrics["checks"]["standin_impl"] == "standin-kernel"
    assert set(metrics["checks"]) >= {"compiles_in_window", "reference"}
    assert not {"ragged_impl", "paged_impl"} & set(metrics["checks"])
    assert metrics["checks"]["reference"]["checked"] > 0
    assert metrics["per_layer"]["standin.finished_requests"]["value"] > 0
    assert {"ttft_mean_ms", "tpot_p90_ms", "serve_tok_per_s",
            "setup_s"} <= set(metrics["end_to_end"])
    assert _hashes(copy) == after  # the run wrote only where runs write
