"""The plain reference against ``ServeEngine`` on the CPU, at a small
size of each configuration, over the prefill logits and the decode
logits through the K/V cache."""

import json
from pathlib import Path

import jax
import numpy as np
import pytest

from fqabench import harness

BENCH = Path(__file__).resolve().parents[1]
SMALL = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, num_hidden_layers=2,
             vocab_size=512, torch_dtype="float32")
#: largest |engine - reference| prefill logit as a share of the largest
#: reference logit.  With exact activations both compute the same float32
#: math and differ by summation order alone (1e-6 measured).  The PPA
#: tables quantize an activation's input to 8 fractional bits: half a grid
#: step (2^-9) moves sigmoid by at most 2^-11, about 5e-4, and each exp2
#: softmax weight by about 1.4e-3 relative (5e-4 measured).
PREFILL_TOL = {"exact": 1e-4, "ppa": 3e-3}
#: decode logits read K/V from the engine's cache, which holds them in
#: bfloat16 whatever the compute dtype: each entry is rounded by up to
#: 2^-9 relative, which two layers carry into the logits at under 1e-2
#: (7e-3 measured); the reference keeps K/V in float32.
DECODE_TOL = 2e-2


def small_conf(name, act_impl):
    conf = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    conf.update(SMALL, act_impl=act_impl)
    conf.pop("act_backend", None)        # the CPU runs the default path
    return conf


def engine_logits(conf, seed, prompts, max_new):
    """Serve each prompt alone through ServeEngine; returns the served
    tokens and every logits row the engine sampled from, in order."""
    from repro.serve import Request, ServeEngine

    seen = []

    class Recording(ServeEngine):
        def _sample_rows(self, logits, temps, keys):
            seen.append(np.asarray(logits, np.float32)[0])
            return super()._sample_rows(logits, temps, keys)

    program = harness.load_program(BENCH, conf)
    cfg = program.program_cfg(conf)
    eng = Recording(cfg, program.program_params(cfg, seed), n_slots=1,
                    cache_len=64)
    served, rows = [], []
    for i, p in enumerate(prompts):
        seen.clear()
        r = Request(rid=i, prompt=p, max_new_tokens=max_new)
        eng.submit(r)
        eng.run_until_drained()
        served.append(list(r.output))
        rows.append(np.stack(seen))
    return served, rows


@pytest.mark.parametrize("act_impl", ["exact", "ppa"])
@pytest.mark.parametrize("name", ["internlm2-1.8b", "mistral-nemo-12b"])
def test_reference_matches_engine(name, act_impl):
    conf = small_conf(name, act_impl)
    ref_mod = harness.load_reference(BENCH, conf["reference"])
    seed = 2**31 + 5
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 512, n).astype(np.int32) for n in (9, 23)]
    served, rows = engine_logits(conf, seed, prompts, max_new=6)

    ref = ref_mod.Reference(conf, seed)
    seqs = list(zip(prompts, served))
    tokens, where = ref_mod.batch_tokens(seqs, rows=2, length=40)
    with jax.default_matmul_precision("highest"):
        hs = ref.hidden(tokens)[tuple(np.asarray(a) for a in zip(*where))]
        want = np.concatenate([np.asarray(lg)
                               for _, lg in ref.logits_rows(hs)])[:len(where)]
    got = np.concatenate(rows)
    assert got.shape == want.shape == (12, 512)
    rel = np.abs(got - want).max(axis=1) / np.abs(want).max()
    prefill_rows = [0, 6]                 # the first row of each request
    assert rel[prefill_rows].max() < PREFILL_TOL[act_impl], rel
    assert np.delete(rel, prefill_rows).max() < DECODE_TOL, rel
    # the first served token of each request is the prefill's choice
    assert served[0][0] == int(np.argmax(want[0]))
