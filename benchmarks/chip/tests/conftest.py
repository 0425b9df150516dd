"""The benchmark's tests import its package and the program the way
``run.py`` does; nothing here touches a TPU."""

import json
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
for p in (BENCH, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

#: the small cell's limit.  At this size bf16 serving reads a
#: root-mean-square logit error of 0.0038-0.0095 over seeds 1-6 and
#: 2**31 + 77; the int8 control reads 0.0378-0.063 on the same seeds; a
#: token altered where it is made reads 1.13, and a decode step that keeps
#: its input cache 0.55 (CPU readings, taken when the limit was set).
SMALL_LIMITS = {"logit_err_rms": {"limit": 0.02}}


@pytest.fixture(autouse=True)
def keep_cache_dir():
    """A cell points JAX's compilation cache into its checkout; give the
    process its setting back for the tests that follow."""
    import jax
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


@pytest.fixture(scope="session")
def small_root(tmp_path_factory):
    return make_small_root(tmp_path_factory.mktemp("cell"))


def make_small_root(root: Path) -> Path:
    """A checkout at ``root`` holding one small cell, ``small.small``: its
    own BENCHMARK.json, config, mix and limits, and the real program
    modules, reference and per-layer readers."""
    bd = root / "benchmarks" / "chip"
    for sub in ("programs", "reference", "layer_metrics"):
        shutil.copytree(BENCH / sub, bd / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    for sub in ("configs", "traffic", "limits"):
        (bd / sub).mkdir(parents=True)
    conf = json.loads((BENCH / "configs" / "internlm2-1.8b.json").read_text())
    conf.update(name="small", hidden_size=256, intermediate_size=512,
                num_attention_heads=4, num_key_value_heads=2, head_dim=64,
                num_hidden_layers=2, vocab_size=512,
                act_impl="exact")
    conf.pop("act_backend")
    (bd / "configs" / "small.json").write_text(json.dumps(conf))
    (bd / "traffic" / "small.json").write_text(json.dumps({
        "kind": "open_loop", "rate_per_s": 6.0, "n_slots": 3,
        "cache_len": 64, "check_requests": 3,
        "prompt": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                   "min": 9, "max": 32},
        "output": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                   "min": 4, "max": 16}}))
    (bd / "limits" / "small.small.json").write_text(json.dumps(SMALL_LIMITS))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["workloads"] = [{"name": "small.small", "config": "small",
                          "traffic": "small", "chips": 1, "why": "test"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        m["workloads"] = ["small.small"]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root
