"""Launch-layer integration: mesh/sharding units in-process, plus one real
multi-pod dry-run in a subprocess (needs its own XLA device-count flag)."""

import json
import os
import subprocess
import sys

import pytest

from repro.arch.config import ArchConfig, LayerSpec
from repro.configs import ARCHS, get_config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_all_archs_have_configs():
    assert len(ARCHS) == 10
    fams = {get_config(a).family for a in ARCHS}
    assert fams == {"audio", "moe", "vlm", "dense", "hybrid", "ssm"}


def test_reduced_configs_meet_smoke_budget():
    for a in ARCHS:
        cfg = get_config(a).reduced()
        assert cfg.d_model <= 512
        assert cfg.n_layers <= 4
        if cfg.n_experts:
            assert cfg.n_experts <= 4


def test_partitioner_divisibility_fallbacks():
    import numpy as np

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.launch.sharding import Partitioner

    if jax.device_count() < 1:
        pytest.skip("no devices")
    dev = np.asarray(jax.devices()[:1]).reshape(1, 1)
    mesh = Mesh(dev, ("data", "model"))
    cfg = get_config("qwen2-0.5b")
    part = Partitioner(mesh, cfg)
    # with model axis of size 1 everything divides; specs must be coherent
    import jax.numpy as jnp
    specs = part.param_specs({"embed": jnp.zeros((8, 4)),
                              "lm_head": jnp.zeros((4, 8)),
                              "blocks": ({"attn": {"wq": jnp.zeros((1, 4, 4))}},)})
    assert specs["embed"] == P("model", None)


@pytest.mark.slow
def test_dryrun_subprocess_decode():
    """One real lower+compile on the 16x16 mesh (smallest combo)."""
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "qwen2-0.5b", "--shape", "long_500k"],
        cwd=REPO, env={**os.environ, "PYTHONPATH": "src"},
        capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout


def test_serve_engine_batches_requests():
    import jax
    import numpy as np
    from repro.arch.model import TransformerLM
    from repro.serve.lm_wave import ServeEngine

    cfg = get_config("qwen2-0.5b").reduced(d_model=32)
    model = TransformerLM(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    eng = ServeEngine(model, params, cache_len=48)
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(0, cfg.vocab, n)) for n in (5, 5, 9)]
    outs, stats = eng.generate(prompts, max_new=4)
    assert all(len(o) == 4 for o in outs)
    # 2 prompt-length types + 3 decode waves
    assert stats.n_prefill_batches == 2
    assert stats.n_decode_batches == 3


def test_chip_smoke_refuses_a_non_tpu_backend():
    """chip_smoke.py's device guard: no result off a TPU (here, the CPU)."""
    import importlib.util

    import jax

    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)
    with pytest.raises(SystemExit, match="needs a TPU"):
        chip_smoke.require_tpu(jax.devices())
