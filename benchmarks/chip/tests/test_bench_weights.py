"""Weight rules: a role without a rule is an error that names it, and a
rule a program module adds draws the same bits stacked over a model's
layers, as the program's parameter tree is drawn, and one layer at a
time, as a reference draws them."""

import json
from pathlib import Path

import numpy as np
import pytest

from fqabench import harness, model, weights

BENCH = Path(__file__).resolve().parents[1]
TOY = BENCH / "tests" / "data" / "toy_hybrid"


def toy_program():
    conf = json.loads((TOY / "configs" / "toy-hybrid.json").read_text())
    return conf, harness.load_program(TOY, conf)


def test_unknown_role_raises_and_names_it():
    key = weights.base_key(7)
    with pytest.raises(KeyError, match="'router/w'"):
        weights.draw(key, "router/w", 0, (4, 8))
    _, program = toy_program()
    with pytest.raises(KeyError, match="'router/w'"):
        weights.draw(key, "router/w", 0, (4, 8), program.ROLES)


def test_extra_rules_add_roles_and_leave_dense_bits():
    key = weights.base_key(2**40 + 3)
    _, program = toy_program()
    for role in ("wq", "ln1/scale", "embed"):
        shape = (16,) if role.endswith("scale") else (32, 16)
        a = weights.draw(key, role, 2, shape)
        b = weights.draw(key, role, 2, shape, program.ROLES)
        assert np.array_equal(np.asarray(a), np.asarray(b))
    a_log = np.asarray(weights.draw(key, "a_log", 0, (64, 8),
                                    program.ROLES))
    assert 0.0 <= a_log.min() and a_log.max() < 2.0      # center 1, width 2


def test_module_rule_draws_the_same_bits_stacked_and_by_layer():
    """The program's tree (every layer of a stage drawn at once, under
    ``vmap``) holds, for each layer, what one draw of that layer gives,
    with the layer counted over the whole model."""
    conf, program = toy_program()
    cfg = program.program_cfg(conf)
    seed = 2**33 + 1
    params = program.program_params(cfg, seed)
    key = weights.base_key(seed)
    first = 0
    for i, st in enumerate(cfg.stages):
        stage = params["stages"][f"s{i}_hyb"]
        for group, role in (("ssm", "a_log"), ("ssm", "w_in"),
                            ("ssm", "dt_bias"), ("attn", "wq"),
                            ("ln1", "scale")):
            stacked = np.asarray(stage[group][role])
            name = f"{group}/{role}" if role == "scale" else role
            for j in range(st.n_layers):
                one = weights.draw(key, name, first + j, stacked.shape[1:],
                                   program.ROLES)
                assert np.array_equal(
                    stacked[j], np.asarray(one.astype(cfg.param_dtype))), \
                    (i, name, j)
        first += st.n_layers
    # layer 0 of the second stage is the model's layer 1, not layer 0
    assert not np.array_equal(np.asarray(params["stages"]["s1_hyb"]["ssm"]
                                         ["w_in"][0]),
                              np.asarray(params["stages"]["s0_hyb"]["ssm"]
                                         ["w_in"][0]))


def test_first_layer_of_each_stage():
    stages = [type("S", (), {"n_layers": n}) for n in (1, 14, 1)]
    assert model._first_layer(["embed"], stages) == 0
    assert model._first_layer(["stages", "s0_hyb", "ssm", "w_in"],
                              stages) == 0
    assert model._first_layer(["stages", "s2_hyb", "attn", "wq"],
                              stages) == 15
