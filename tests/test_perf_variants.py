"""Correctness of the §Perf variants: int8 MoE weight gather, sp_tp and
dp_only strategies, D1 cache sharding — all must preserve semantics
(subprocess: needs >1 host device)."""

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
    import dataclasses, json, sys
    import jax, jax.numpy as jnp
    import numpy as np

    from repro.configs.base import get_config
    from repro.configs.shapes import ShapeSpec, synthesize_batch
    from repro.launch.mesh import make_ctx, make_debug_mesh
    from repro.models.registry import build_model
    from repro.parallel.ctx import ParallelCtx
    from repro.train.step import make_loss_fn

    mode = sys.argv[1]
    mesh = make_debug_mesh(4, 2)
    arch = "moonshot-v1-16b-a3b" if mode == "int8moe" else "qwen3-4b"
    cfg = get_config(arch).smoke()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = synthesize_batch(cfg, ShapeSpec("t", 64, 8, "train"), seed=0)

    ref_pctx = ParallelCtx(mesh=None)
    ref_loss, _ = make_loss_fn(model, cfg, ref_pctx)(params, batch)

    if mode == "int8moe":
        pctx = dataclasses.replace(make_ctx(mesh), int8_moe_gather=True)
        tol = 0.05   # quantized weights: close but not exact
    elif mode == "sp_tp":
        pctx = make_ctx(mesh, strategy="sp_tp")
        tol = 1e-3
    else:
        pctx = make_ctx(mesh, strategy="dp_only")
        tol = 1e-3

    with mesh:
        loss_fn = make_loss_fn(model, cfg, pctx)
        loss, _ = jax.jit(loss_fn)(params, batch)
        grads = jax.jit(jax.grad(lambda p: loss_fn(p, batch)[0]))(params)
    gfinite = all(bool(jnp.isfinite(g).all()) for g in jax.tree.leaves(grads))
    rel = abs(float(loss) - float(ref_loss)) / max(abs(float(ref_loss)), 1e-9)
    print(json.dumps({"ok": bool(rel < tol and gfinite),
                      "rel": rel, "gfinite": gfinite}))
    """
)


@pytest.mark.parametrize("mode", ["int8moe", "sp_tp", "dp_only"])
def test_perf_variant_preserves_loss(mode):
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT, mode],
        capture_output=True, text=True, timeout=900,
        env={"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin",
             "HOME": "/root", "JAX_PLATFORMS": "cpu"},
    )
    assert r.returncode == 0, f"{mode} failed:\n{r.stdout[-2000:]}\n{r.stderr[-3000:]}"
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["ok"], out
