"""Tests of the benchmark itself, on the CPU at small sizes.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests

They import ``bench`` and the program from the checkout. The repository's
own test run (``testpaths = tests``) does not collect them.
"""
import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# four host devices, for the cells laid over four chips
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")


def make_root(tmp_path, cells):
    """A checkout holding the benchmark's files plus new cells.

    ``cells``: ``[(cell, config, traffic, limits)]`` where ``cell`` is a
    ``BENCHMARK.json`` workload entry and the others are the new files'
    contents (None keeps an existing file). Nothing already there is
    edited: the new cell arrives as new files and a new entry."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    os.symlink(os.path.join(ROOT, "src"), root / "src")
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for cell, config, traffic, limits in cells:
        b = root / "bench"
        for sub, name, body in (("configs", cell["config"], config),
                                ("traffic", cell["traffic"], traffic),
                                ("limits", cell["name"], limits)):
            if body is not None:
                path = b / sub / (name + ".json")
                assert not path.exists(), path
                path.write_text(json.dumps(body))
        spec["workloads"].append(cell)
        for m in spec["end_to_end"] + spec["per_layer"]:
            if "workloads" in m and any(
                    w in m["workloads"] for w in cell.get("like", [])):
                m["workloads"].append(cell["name"])
        cell.pop("like", None)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return str(root)


def tiny_classifier_cell(name="mlp-c8.train"):
    config = json.load(open(os.path.join(ROOT, "bench", "configs",
                                         "mlp-c64.json")))
    config["parties"] = 8
    traffic = json.load(open(os.path.join(ROOT, "bench", "traffic",
                                          "rows256.json")))
    traffic["batch"] = 32
    cell = {"name": name, "config": "mlp-c8", "traffic": "rows32",
            "chips": 1, "why": "a small classifier federation for the tests",
            "like": ["mlp-c64.train"]}
    # at this size on the CPU, float32 matrix products are exact float32:
    # sound runs read about 1e-7, the bfloat16 control 5e-4 and up
    limits = {"loss_gap": 1e-5, "grad_gap": 1e-5, "change_gap": 1e-5}
    return cell, config, traffic, limits


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("tiny"),
                     [tiny_classifier_cell()])


TINY_LM = {"hidden_size": 64, "intermediate_size": 128,
           "num_attention_heads": 4, "num_key_value_heads": 2,
           "num_hidden_layers": 4, "vocab_size": 256, "head_dim": 16}


def tiny_lm_config():
    """The language-model configuration at small widths, for the CPU."""
    config = json.load(open(os.path.join(ROOT, "bench", "configs",
                                         "qwen2-1.5b-c4-l8.json")))
    config.update(TINY_LM)
    config["program_arch"] = "tiny-qwen2"
    return config


@pytest.fixture
def tiny_lm_arch(monkeypatch):
    """Registers the tiny widths under ``tiny-qwen2`` in the program."""
    import dataclasses
    from repro.configs import base
    orig = base.get_config

    def get(name):
        if name != "tiny-qwen2":
            return orig(name)
        q = orig("qwen2-1.5b")
        return dataclasses.replace(
            q, name="tiny-qwen2", d_model=64, d_ff=128, n_heads=4,
            n_kv_heads=2, head_dim=16, vocab_size=256, n_layers=4)

    monkeypatch.setattr(base, "get_config", get)


def tiny_lm_train_cell():
    traffic = json.load(open(os.path.join(ROOT, "bench", "traffic",
                                          "seq512x2.json")))
    traffic.update({"seq": 32, "chunk": 4})
    cell = {"name": "tiny-lm.train", "config": "tiny-lm",
            "traffic": "seq32x2", "chips": 1,
            "why": "a small language-model federation for the tests",
            "like": ["qwen2-1.5b-c4-l8.train"]}
    # on the CPU, bfloat16 against float32: sound runs read about 1e-3
    limits = {"loss_gap": 0.02, "change_gap": 0.1}
    return cell, tiny_lm_config(), traffic, limits


def tiny_lm_serve_cell():
    traffic = json.load(open(os.path.join(ROOT, "bench", "traffic",
                                          "closed32.json")))
    traffic.update({"lanes": 4, "max_len": 64, "chunk": 4, "clients": 4,
                    "prompt": {"median": 10, "sigma": 0.8, "min": 2,
                               "max": 32, "buckets": [8, 16, 32]},
                    "output": {"median": 6, "sigma": 0.9, "min": 2,
                               "max": 24},
                    "pool": 256, "warmup_seconds": 0.5, "check_tokens": 40})
    cell = {"name": "tiny-lm.serve", "config": "tiny-lm",
            "traffic": "closed4", "chips": 1,
            "why": "a small served language-model federation for the tests",
            "like": ["qwen2-1.5b-c4-l8.serve-closed32"]}
    # bfloat16 program against the float32 reference at these widths:
    # near-ties put a served token a few hundredths below the best
    limits = {"logit_gap": 0.25}
    return cell, tiny_lm_config(), traffic, limits


def tiny_sharded_cell():
    """16 parties (four groups of four) laid over four host devices."""
    cell, config, traffic, limits = tiny_classifier_cell("mlp-c16.train-4chip")
    config["parties"] = 16
    traffic = json.load(open(os.path.join(ROOT, "bench", "traffic",
                                          "rows256-sharded4.json")))
    traffic["batch"] = 32
    cell.update(config="mlp-c16", traffic="rows32-sharded4", chips=4,
                like=["mlp-c64.train-4chip"])
    return cell, config, traffic, limits
