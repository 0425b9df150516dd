"""Each configuration names its program module, and the harness builds
the program, draws its weights and counts its work through that module
alone: the dense cells keep the program, the weights and the counts they
had when the dense builder sat in the harness, and a hybrid (attention
and SSM) family joins the benchmark as new files only."""

import dataclasses
import filecmp
import hashlib
import json
import shutil
from pathlib import Path

import jax
import numpy as np
import pytest

from fqabench import harness, yardstick

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
TOY = BENCH / "tests" / "data" / "toy_hybrid"
DENSE = ("internlm2-1.8b", "mistral-nemo-12b")

#: the ModelCfg fields the dense builder sets from a configuration file;
#: every other field is the program's own configuration of the named
#: architecture
PINNED_CFG = {
    "internlm2-1.8b": dict(
        arch="internlm2-1.8b", d_model=2048, n_q=16, n_kv=8, head_dim=128,
        d_ff=8192, vocab=92544, n_layers=24, rope_theta=1e6,
        tie_embeddings=False),
    "mistral-nemo-12b": dict(
        arch="mistral-nemo-12b", d_model=5120, n_q=32, n_kv=8, head_dim=128,
        d_ff=14336, vocab=131072, n_layers=10, rope_theta=1e6,
        tie_embeddings=False),
}
#: a small parameter tree of each dense configuration (sizes below, seed
#: 2**31 + 5, bf16), hashed leaf by leaf in tree order; the digests were
#: taken from the dense builder before it moved into programs/dense_gqa.py
SMALL = {
    "internlm2-1.8b": dict(hidden_size=64, intermediate_size=128,
                           num_attention_heads=4, num_key_value_heads=2,
                           head_dim=16, num_hidden_layers=2, vocab_size=512),
    "mistral-nemo-12b": dict(hidden_size=64, intermediate_size=96,
                             num_attention_heads=8, num_key_value_heads=2,
                             head_dim=16, num_hidden_layers=3,
                             vocab_size=256),
}
PINNED_DIGEST = {
    "internlm2-1.8b":
        "b9ace799be35096d76477dd4cb254e95cfaac7a0125b88e18a83dd5ad98229d7",
    "mistral-nemo-12b":
        "dce3f92e4606705fe91fc0993a63f511293a30ca875848f5ada6f595152d10e4",
}
#: prefill_flops(1024), decode_flops(2048),
#: decode_step_bytes([300, 1024, 2048]) at the configurations' own sizes
PINNED_COUNTS = {
    "internlm2-1.8b": (3195935391744.0, 3801612288.0, 3730653184),
    "mistral-nemo-12b": (5670782894080.0, 7130316800.0, 6933135360),
}


def digest(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name", DENSE)
def test_dense_program_cfg_is_pinned(name):
    from repro.configs import get_config
    from repro.models import StageCfg

    conf = harness.load_config(BENCH, name)
    cfg = harness.load_program(BENCH, conf).program_cfg(conf)
    pin = dict(PINNED_CFG[name])
    pin["stages"] = (StageCfg("dec", pin.pop("n_layers")),)
    want = get_config(conf["program_arch"]).replace(
        **pin, act_impl="ppa", act_backend="pallas_fused",
        param_dtype="bfloat16", compute_dtype="bfloat16")
    assert dataclasses.asdict(cfg) == dataclasses.asdict(want)


@pytest.mark.parametrize("name", DENSE)
def test_dense_params_are_pinned(name):
    conf = dict(harness.load_config(BENCH, name),
                **SMALL[name])
    program = harness.load_program(BENCH, conf)
    params = program.program_params(program.program_cfg(conf), 2**31 + 5)
    assert digest(params) == PINNED_DIGEST[name]


@pytest.mark.parametrize("name", DENSE)
def test_dense_counts_are_pinned(name):
    conf = harness.load_config(BENCH, name)
    counts = harness.load_program(BENCH, conf).counts(conf)
    assert (counts.prefill_flops(1024), counts.decode_flops(2048),
            counts.decode_step_bytes([300, 1024, 2048])) \
        == PINNED_COUNTS[name]


def test_a_config_without_its_program_is_an_error(tmp_path):
    (tmp_path / "programs").mkdir()
    with pytest.raises(KeyError, match=r"configs/x\.json names no program"):
        harness.load_program(tmp_path, {"name": "x"})
    with pytest.raises(FileNotFoundError,
                       match=r"configs/x\.json names program 'gone'.*"
                             r"programs/gone\.py"):
        harness.load_program(tmp_path, {"name": "x", "program": "gone"})


def test_a_program_may_not_redraw_a_dense_role(tmp_path):
    (tmp_path / "programs").mkdir()
    (tmp_path / "programs" / "greedy.py").write_text(
        "from fqabench import weights\n"
        "ROLES = {'wq': weights.uniform(1.0)}\n")
    with pytest.raises(ValueError, match=r"\['wq'\]"):
        harness.load_program(tmp_path, {"name": "x", "program": "greedy"})


# ------------------------------------------------- a hybrid joins by files
def _copy_checkout(dest: Path) -> Path:
    shutil.copy(ROOT / "BENCHMARK.json", dest)
    shutil.copytree(BENCH, dest / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns(".*", "__pycache__"))
    return dest / "benchmarks" / "chip"


def _add_toy(bd: Path) -> list:
    """Copy the toy hybrid's files into the checkout; each must be new."""
    added = []
    for src in sorted(p for p in TOY.rglob("*") if p.is_file()
                      and "__pycache__" not in p.parts):
        dst = bd / src.relative_to(TOY)
        assert not dst.exists(), dst
        shutil.copy(src, dst)
        added.append(dst)
    return added


def test_a_hybrid_joins_by_new_files_alone(tmp_path):
    bd = _copy_checkout(tmp_path)
    added = set(_add_toy(bd))
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": "toy-hybrid", "source": "https://arxiv.org/abs/2411.13676",
        "file": "benchmarks/chip/configs/toy-hybrid.json", "reduced": [],
        "why": "attention and SSM heads side by side"})
    spec["workloads"].append({
        "name": "toy-hybrid.toy", "config": "toy-hybrid", "traffic": "toy",
        "chips": 1, "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    seed = 2**31 + 11
    cell = harness.Cell("toy-hybrid.toy", seed, root=tmp_path, bench_dir=bd,
                        require_tpu=False)
    assert {st.kind for st in cell.cfg.stages} == {"hyb"}
    assert [st.window for st in cell.cfg.stages] == [None, 8]
    assert cell.eng._bucket_len(12) == 12          # hybrid prompts: unpadded
    w, t_end, compiles = cell.measure(seed, 2.0)
    assert compiles == 0
    seqs = cell.sample(w, seed)
    assert seqs and harness.judge(cell.check(seed, seqs))

    # the whole step's share of the peak, through the module's counts
    counts = cell.counts
    assert type(counts).__module__ == "program_toy_hybrid"
    run = harness.Run(counts, cell.peaks, t_end - w.t0, w.steps, 0.0, None)
    flops = sum(counts.prefill_flops(t) for s in w.steps
                for t in s.prefill_lens) + sum(
        counts.decode_flops(k) for s in w.steps for k in s.decode_keys)
    assert flops > 0
    want = 100.0 * flops / (run.window_s * cell.peaks["bf16_flops_per_s"])
    mfu = [m["name"] for m in spec["per_layer"]
           if m["name"].startswith("model.mfu.")]
    assert mfu
    for name in mfu:
        assert harness.load_reader(bd, name)(run) == pytest.approx(want)
    # the SSM adds work a dense decoder of the same widths does not have
    dense = yardstick.Dims.from_config(cell.conf)
    assert counts.decode_flops(20) > dense.decode_flops(20)

    # nothing that was already in the checkout changed
    for path in bd.rglob("*"):
        if path.is_file() and path not in added \
                and "__pycache__" not in path.parts \
                and not any(p.startswith(".") for p in
                            path.relative_to(bd).parts):
            assert filecmp.cmp(path, BENCH / path.relative_to(bd),
                               shallow=False), path
