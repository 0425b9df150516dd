"""Distribution-layer tests on an 8-device host-platform mesh.

These run in subprocesses because the fake-device count must be set before
jax initializes (the main test process keeps 1 device per the assignment).
Covered: sharded-MoE == local-MoE bit-level agreement, int8 error-feedback
allreduce convergence, pipeline_apply == sequential scan, sharding-rule
construction, checkpoint resharding across different meshes.
"""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]


def _run(body: str, n_dev: int = 8) -> str:
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count={n_dev}")
        os.environ["JAX_PLATFORMS"] = "cpu"
        {textwrap.indent(textwrap.dedent(body), '        ').strip()}
    """)
    r = subprocess.run(
        [sys.executable, "-c", code],
        env=dict(os.environ, PYTHONPATH=str(REPO / "src")),
        capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-4000:]}"
    return r.stdout


@pytest.mark.dryrun
def test_moe_sharded_matches_local():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.models import ShardCtx, init_params, make_acts
        from repro.models.moe import MoECfg, moe_params, moe_block
        from repro.models.common import P

        mesh = jax.make_mesh((2, 4), ("data", "model"))
        cfg = MoECfg(d_model=32, d_ff=16, n_experts=8, top_k=2,
                     capacity_factor=8.0)
        specs = moe_params(cfg)
        params = init_params(specs, jax.random.PRNGKey(0))
        acts = make_acts("exact")
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 32), jnp.float32)

        y_loc, aux_loc = moe_block(params, x, cfg, acts, ShardCtx())
        ctx = ShardCtx(mesh=mesh, dp_axes=("data",))
        y_sh, aux_sh = jax.jit(
            lambda p, v: moe_block(p, v, cfg, acts, ctx))(params, x)
        np.testing.assert_allclose(np.asarray(y_loc), np.asarray(y_sh),
                                   atol=2e-5, rtol=1e-4)
        # aux: per-data-shard switch loss averaged != global switch loss
        # (nonlinear in the token partition); agreement only approximate
        np.testing.assert_allclose(float(aux_loc), float(aux_sh), rtol=0.25)

        # token_gather mode must agree too
        cfg_tg = MoECfg(d_model=32, d_ff=16, n_experts=8, top_k=2,
                        capacity_factor=8.0, mode="token_gather")
        y_tg, _ = jax.jit(
            lambda p, v: moe_block(p, v, cfg_tg, acts, ctx))(params, x)
        np.testing.assert_allclose(np.asarray(y_loc), np.asarray(y_tg),
                                   atol=2e-5, rtol=1e-4)
        print("MOE_OK")
    """)
    assert "MOE_OK" in out


@pytest.mark.dryrun
def test_embed_lookup_on_vocab_sharded_local_mesh():
    """``make_local_mesh`` makes every axis Auto, so the embedding
    gather on a vocab-sharded table compiles (an Explicit axis makes it a
    sharding-type error) and matches the unsharded gather."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as PS
        from repro.distributed import make_ctx
        from repro.launch.mesh import make_local_mesh
        from repro.models.layers import embed_lookup

        mesh = make_local_mesh(4)
        assert dict(mesh.shape) == {"data": 1, "model": 4}
        ctx = make_ctx(mesh)
        table = jax.random.normal(jax.random.PRNGKey(0), (512, 64))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 512)
        sharded = jax.device_put(table, NamedSharding(mesh, PS("model")))
        got = jax.jit(lambda t, x: embed_lookup(t, x, ctx))(sharded, tokens)
        want = jnp.take(table, tokens, axis=0)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        print("EMBED_OK")
    """, n_dev=4)
    assert "EMBED_OK" in out


@pytest.mark.dryrun
def test_ef_allreduce_preserves_sum():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed import ef_allreduce
        from jax.sharding import PartitionSpec as PS

        mesh = jax.make_mesh((8,), ("dp",))
        g = jax.random.normal(jax.random.PRNGKey(0), (8, 64), jnp.float32)

        def body(gl, err):
            mean, new_err = ef_allreduce(gl[0] + err[0], "dp")
            return mean, new_err[None]

        sm = jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=(PS("dp"), PS("dp")),
            out_specs=(PS(), PS("dp")), check_vma=False))
        err = jnp.zeros_like(g)
        exact_accum = jnp.zeros((64,))
        ef_accum = jnp.zeros((64,))
        for step in range(20):
            gs = g * (1.0 + 0.1 * step)
            mean, err = sm(gs, err)
            ef_accum = ef_accum + mean
            exact_accum = exact_accum + gs.mean(0)
        # error feedback: accumulated compressed mean ~ accumulated exact
        rel = float(jnp.abs(ef_accum - exact_accum).max()
                    / jnp.abs(exact_accum).max())
        assert rel < 0.02, rel
        print("EF_OK", rel)
    """)
    assert "EF_OK" in out


@pytest.mark.dryrun
def test_pipeline_matches_sequential():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed import pipeline_apply, bubble_fraction

        mesh = jax.make_mesh((4,), ("pod",))
        L, B, T, D = 8, 8, 4, 16
        w = jax.random.normal(jax.random.PRNGKey(0), (L, D, D)) * 0.1
        h = jax.random.normal(jax.random.PRNGKey(1), (B, T, D))

        def body(x, wl):
            return jnp.tanh(x @ wl)

        ref = h
        for i in range(L):
            ref = body(ref, w[i])

        out = jax.jit(lambda ww, hh: pipeline_apply(
            body, ww, hh, mesh, n_micro=4, axis="pod"))(w, h)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                   atol=1e-5, rtol=1e-4)
        assert abs(bubble_fraction(4, 4) - 3/7) < 1e-9
        print("PP_OK")
    """)
    assert "PP_OK" in out


@pytest.mark.dryrun
def test_checkpoint_reshard_across_meshes():
    """Save on a (4,2) mesh, restore onto (2,4) — elastic restart."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np, tempfile
        from jax.sharding import NamedSharding, PartitionSpec as PS
        from repro.checkpoint import save, restore

        m1 = jax.make_mesh((4, 2), ("data", "model"))
        m2 = jax.make_mesh((2, 4), ("data", "model"))
        x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
        x1 = jax.device_put(x, NamedSharding(m1, PS("data", "model")))
        d = tempfile.mkdtemp()
        save(d, 1, {"w": x1}, extra={"next_step": 1})
        like = {"w": jax.ShapeDtypeStruct((8, 8), jnp.float32)}
        sh2 = {"w": NamedSharding(m2, PS("data", "model"))}
        restored, _ = restore(d, 1, like, sh2)
        assert restored["w"].sharding == sh2["w"]
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.asarray(x))
        print("RESHARD_OK")
    """)
    assert "RESHARD_OK" in out


def test_sharding_rules_tables():
    """Rule construction is pure — no devices needed."""
    import numpy as np
    import jax
    from jax.sharding import PartitionSpec as PS
    from repro.distributed.sharding import _spec_for, make_rules

    class FakeMesh:
        axis_names = ("pod", "data", "model")

    rules = make_rules("train", FakeMesh())
    assert rules["mlp"] == "model"
    assert tuple(rules["embed"]) == ("pod", "data")
    assert _spec_for(("embed", "mlp"), rules) == PS(("pod", "data"), "model")
    # conflicting reuse of a mesh axis degrades to None
    assert _spec_for(("mlp", "q_heads"), rules) == PS("model", None)
    serve = make_rules("serve", FakeMesh())
    assert serve["expert_mlp"] and serve["expert_embed"] is None
