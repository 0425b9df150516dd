"""A dense decoder with grouped-query attention, RoPE, RMSNorm and a
SwiGLU MLP (internlm2, mistral-nemo), built by the program from a
configuration file that names ``"program": "dense_gqa"``.

The program's own configuration of the named architecture supplies
everything the file does not state (attention path, remat, and the
activation backend unless the file pins one), so a change to those
defaults is measured; every size the file states overrides it.  Its
weights are the dense roles of :mod:`fqabench.weights`, and its work
counts are :class:`fqabench.yardstick.Dims`.
"""

from __future__ import annotations

from fqabench import model, yardstick


def program_cfg(conf: dict):
    """``repro`` ModelCfg of a dense GQA decoder as ``conf`` states it."""
    from repro.configs import get_config
    from repro.models import StageCfg

    base = get_config(conf["program_arch"])
    if base.family != "dense" or any(s.kind != "dec" or s.moe or s.window
                                     for s in base.stages):
        raise ValueError(f"{conf['name']}: {conf['program_arch']} is a "
                         f"{base.family} model; dense_gqa builds only "
                         "plain decoder stages")
    return base.replace(
        arch=conf["name"],
        d_model=conf["hidden_size"], n_q=conf["num_attention_heads"],
        n_kv=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        d_ff=conf["intermediate_size"], vocab=conf["vocab_size"],
        stages=(StageCfg("dec", conf["num_hidden_layers"]),),
        rope_theta=float(conf["rope_theta"]),
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        act_impl=conf["act_impl"],
        act_backend=conf.get("act_backend", base.act_backend),
        param_dtype=conf["torch_dtype"],
        compute_dtype=conf["torch_dtype"])


def program_params(cfg, seed: int):
    return model.draw_params(cfg, seed)


def counts(conf: dict) -> yardstick.Dims:
    return yardstick.Dims.from_config(conf)
