"""Sharded lowering sanity tests on an 8-device debug mesh (subprocess so the
XLA host-device-count flag doesn't leak into other tests)."""

import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

SRC = str(Path(__file__).resolve().parent.parent / "src")

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import functools, json, sys
    import jax, jax.numpy as jnp

    from repro.configs.base import get_config
    from repro.configs.shapes import ShapeSpec, input_specs, synthesize_batch
    from repro.launch.mesh import make_ctx, make_debug_mesh
    from repro.models.registry import build_model
    from repro.optim import adamw
    from repro.parallel.sharding import batch_spec, param_specs
    from repro.train.step import init_train_state, make_train_step
    from jax.sharding import NamedSharding

    arch = sys.argv[1]
    mode = sys.argv[2]
    mesh = make_debug_mesh(4, 2)
    pctx = make_ctx(mesh, remat="full")
    cfg = get_config(arch).smoke()
    model = build_model(cfg)
    opt = adamw(1e-3)

    shape = ShapeSpec("t", seq_len=64, global_batch=8, kind=mode)
    batch = synthesize_batch(cfg, shape, seed=0)

    with mesh:
        if mode == "train":
            state = init_train_state(model, cfg, opt, jax.random.PRNGKey(0), max_dec_len=128)
            p_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                param_specs(state.params, cfg, pctx))
            step = jax.jit(make_train_step(model, cfg, pctx, opt))
            state2, metrics = step(state, batch)
            loss = float(metrics["loss"])
            assert jnp.isfinite(metrics["loss"]), "loss not finite"
            state3, m2 = step(state2, batch)
            assert float(m2["loss"]) < loss + 1.0
            print(json.dumps({"ok": True, "loss": loss}))
        else:  # decode
            from repro.serve.steps import make_decode_step
            params = model.init(jax.random.PRNGKey(0), max_dec_len=128)
            caches = model.make_caches(8, 64)
            tok = jnp.zeros((8, 1), jnp.int32)
            pos = jnp.full((8,), 3, jnp.int32)
            step = jax.jit(make_decode_step(model, cfg, pctx))
            logits, caches2 = step(params, caches, tok, pos)
            assert bool(jnp.isfinite(logits).all())
            print(json.dumps({"ok": True}))
    """
)


def _run(arch: str, mode: str):
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT, arch, mode],
        capture_output=True, text=True, timeout=900,
        env={"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin",
             "HOME": "/root", "JAX_PLATFORMS": "cpu"},
    )
    assert r.returncode == 0, f"{arch} {mode} failed:\n{r.stdout}\n{r.stderr}"
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["ok"]


# One representative per family (the full 40-cell sweep runs via dryrun.py).
@pytest.mark.parametrize("arch", [
    "qwen3-4b",          # dense + qk_norm + tied embeddings
    "gemma2-27b",        # local/global pairs + softcaps
    "moonshot-v1-16b-a3b",  # MoE shard_map EP
    "mamba2-1.3b",       # SSM
    "zamba2-7b",         # hybrid
    "whisper-medium",    # enc-dec
    "internvl2-2b",      # vlm frontend
])
def test_sharded_train_step(arch):
    _run(arch, "train")


@pytest.mark.parametrize("arch", ["qwen3-4b", "mamba2-1.3b", "zamba2-7b"])
def test_sharded_decode_step(arch):
    _run(arch, "decode")
