"""End-to-end behaviour tests for the full system (subprocess-based where
multiple fake devices are required)."""
import json
import os

import numpy as np
import pytest


def test_train_driver_lgc_end_to_end(subproc, tmp_path):
    """The train launcher runs all three phases, checkpoints, and reports
    a compression rate."""
    metrics = tmp_path / "m.json"
    out = subproc(f"""
import sys
sys.argv = ["train", "--arch", "llama3.2-1b", "--smoke", "--steps", "12",
            "--batch", "4", "--seq", "64", "--compression", "lgc_rar",
            "--warmup-steps", "2", "--ae-train-steps", "4",
            "--data-shards", "2", "--metrics-out", r"{metrics}",
            "--checkpoint-dir", r"{tmp_path}"]
from repro.launch.train import main
hist = main()
phases = [h["phase"] for h in hist]
assert "warmup" in phases or "topk_ae" in phases
assert hist[-1]["phase"] == "compressed"
import numpy as np
assert np.isfinite([h["loss"] for h in hist]).all()
print("PASS")
""", devices=2, timeout=900)
    assert "PASS" in out
    hist = json.loads(metrics.read_text())
    assert hist[-1]["phase"] == "compressed"
    assert os.path.exists(tmp_path / "ckpt.npz")


@pytest.mark.parametrize("method", ["lgc_ps", "lgc_rar"])
def test_train_driver_single_node_all_three_phases(method):
    """One node (K = 1, the one-chip deployment): both LGC patterns run
    warm-up, top-k + AE training and the compressed phase.  The PS
    pattern stacks its K decoders also at K = 1."""
    from repro.launch.train import main
    hist = main(["--arch", "mamba2-130m", "--smoke", "--steps", "5",
                 "--batch", "2", "--seq", "32", "--compression", method,
                 "--warmup-steps", "1", "--ae-train-steps", "2",
                 "--data-shards", "1", "--log-every", "1"])
    assert [h["phase"] for h in hist] == [
        "warmup", "topk_ae", "topk_ae", "compressed", "compressed"]
    assert np.isfinite([h["loss"] for h in hist]).all()


@pytest.mark.parametrize("env_dir", [True, False])
def test_compile_cache_location(tmp_path, env_dir):
    """enable_compile_cache: entries land in JAX_COMPILATION_CACHE_DIR
    when it is set (and only there), else in <checkout>/.jax_cache."""
    import subprocess
    import sys
    import uuid

    from repro.utils.compile_cache import DEFAULT_DIR
    probe = f"cache_probe_{uuid.uuid4().hex[:12]}"
    code = f"""
import jax, jax.numpy as jnp
from repro.utils.compile_cache import enable_compile_cache
enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
def {probe}(x):
    return jnp.sin(x) * 3
jax.jit({probe})(jnp.ones(5)).block_until_ready()
"""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"), JAX_PLATFORMS="cpu")
    own = tmp_path / "cc"
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(own)

    def entries(d):
        return sorted(d.glob(f"jit_{probe}-*")) if d.exists() else []

    try:
        subprocess.run([sys.executable, "-c", code], env=env, check=True,
                       timeout=300)
        if env_dir:
            assert entries(own) and not entries(DEFAULT_DIR)
        else:
            assert entries(DEFAULT_DIR)
    finally:
        for e in entries(DEFAULT_DIR):
            e.unlink()


def test_serve_driver_end_to_end(subproc):
    out = subproc("""
import sys
sys.argv = ["serve", "--arch", "qwen2-1.5b", "--smoke", "--batch", "2",
            "--prompt-len", "32", "--gen", "6"]
from repro.launch.serve import main
gen = main()
assert gen.shape == (2, 6)
print("PASS")
""", timeout=600)
    assert "PASS" in out


def test_lgc_training_converges_vs_baseline(subproc):
    """Convergence parity (paper Fig. 10/11 at smoke scale): LGC-compressed
    training reaches a loss improvement comparable to dense training."""
    out = subproc("""
import sys, numpy as np
from repro.launch.train import main

def run(method):
    sys.argv = ["t", "--arch", "llama3.2-1b", "--smoke", "--steps", "30",
                "--batch", "8", "--seq", "64", "--compression", method,
                "--warmup-steps", "3", "--ae-train-steps", "6",
                "--sparsity", "0.01", "--log-every", "1",
                "--data-shards", "2", "--lr", "3e-3"]
    return [h["loss"] for h in main()]

dense = run("none")
lgc = run("lgc_rar")
assert dense[-1] < dense[0], "dense did not learn"
assert lgc[-1] < lgc[0], "lgc did not learn"
gain_d = dense[0] - dense[-1]
gain_l = lgc[0] - lgc[-1]
assert gain_l > 0.5 * gain_d, (dense[0], dense[-1], lgc[0], lgc[-1])
print("PASS", dense[-1], lgc[-1])
""", devices=2, timeout=1800)
    assert "PASS" in out


def test_ring_allreduce_matches_psum(subproc):
    out = subproc("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.dist.collectives import ring_allreduce

mesh = jax.make_mesh((4,), ("data",),
                     axis_types=(jax.sharding.AxisType.Auto,))

def f(x):
    ring = ring_allreduce(x[0], "data")
    ref = jax.lax.psum(x[0], "data")
    return jnp.max(jnp.abs(ring - ref))[None]

g = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("data"),
                          out_specs=P("data"), axis_names={"data"},
                          check_vma=False))
for n in [37, 64, 1000]:
    x = jax.random.normal(jax.random.PRNGKey(n), (4, n))
    err = float(jnp.max(g(x)))
    assert err < 1e-5, (n, err)
print("PASS")
""", devices=4, timeout=600)
    assert "PASS" in out


def test_convnet5_paper_model_trains(subproc):
    """The paper's own ConvNet5 (Section VI-E) learns the synthetic image
    task under LGC-compressed distributed training (sim path)."""
    out = subproc("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs.convnet5 import smoke_config
from repro.configs.base import CompressionConfig
from repro.core import build_compressor
from repro.core.phases import phase_for_step
from repro.data import synthetic_image_batches
from repro.models.convnet import convnet5_loss, init_convnet5
from repro.utils.tree import tree_flatten_vector, tree_unflatten_vector

cfg = smoke_config()
K = 4
params = init_convnet5(jax.random.PRNGKey(0), cfg)
cc = CompressionConfig(method="lgc_rar", sparsity=0.05, warmup_steps=10,
                       ae_train_steps=20)
comp = build_compressor(cc, params, K)
states = comp.init_sim_states(jax.random.PRNGKey(1))
data = synthetic_image_batches(cfg.num_classes, K * 8, cfg.image_size)

@jax.jit
def node_grads(params, batch):
    def one(i):
        sl = lambda x: jax.lax.dynamic_slice_in_dim(x, i * 8, 8)
        lb = {"images": sl(batch["images"]), "labels": sl(batch["labels"])}
        (l, m), g = jax.value_and_grad(convnet5_loss, has_aux=True)(
            params, cfg, lb)
        return l, m["accuracy"], tree_flatten_vector(g)
    ls, accs, gs = jax.vmap(one)(jnp.arange(K))
    return ls.mean(), accs.mean(), gs

losses, accs = [], []
params_t = params
step_fn = jax.jit(comp.sim_step, static_argnums=(3,))
for step in range(120):
    batch = next(data)
    loss, acc, g_nodes = node_grads(params_t, batch)
    phase = phase_for_step(step, cc)
    g_vec, states, _ = step_fn(states, g_nodes, step, phase)
    g_tree = tree_unflatten_vector(g_vec, params_t)
    params_t = jax.tree_util.tree_map(lambda p, g: p - 0.08 * g, params_t,
                                      g_tree)
    losses.append(float(loss)); accs.append(float(acc))
assert np.mean(accs[-15:]) > np.mean(accs[:15]) + 0.1, (accs[:5], accs[-5:])
print("PASS acc", np.mean(accs[-10:]))
""", timeout=1800)
    assert "PASS" in out
